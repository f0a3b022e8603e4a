"""A configuration as the program under test takes it: the
``ExperimentConfig`` of ``repro.harness`` on the stacked request path, on
the stacked engine or, where a cell runs on a mesh, the pod engine."""
from __future__ import annotations

# what ``repro.harness`` fixes and the configuration has to agree with
FIXED = {"kappa_max": 5, "chi": 1.0}


def experiment_config(cfg: dict, seed: int, rounds: int, num_users: int,
                      engine: str = "stacked", **knobs):
    """``knobs`` are the traffic's own: the round backend, the slot pool,
    participation, rounds per dispatch."""
    from repro.harness.experiments import ExperimentConfig

    for k, v in FIXED.items():
        if cfg[k] != v:
            raise SystemExit(f"bench: the program runs {k}={v}; the "
                             f"configuration states {cfg[k]}")
    return ExperimentConfig(
        model=cfg["model"], dataset=cfg["dataset"], num_clients=num_users,
        rounds=rounds, capacity=tuple(cfg["capacity"]),
        arrivals=cfg["arrivals"], local_lr=cfg["local_lr"],
        global_lr=cfg["global_lr"], batch=cfg["batch"], topk=cfg["topk"],
        seed=seed, use_resource_opt=cfg["use_resource_opt"],
        engine=engine, request_backend="stacked",
        resource_backend=cfg["resource_backend"],
        cell_radius_m=cfg["client_system"]["cell_radius_m"], **knobs)
