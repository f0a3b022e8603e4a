"""One-dispatch device-resident online OSAFL rounds (ROADMAP "One-dispatch
device-resident rounds + accelerator-native precision").

The multi-dispatch engine (``repro.harness.run`` on the stacked engine
with ``round_backend="dispatch"``) executes one online round as ~7 separate
device programs with host work in between: a host-NumPy Binomial arrival
draw, the stacked Gumbel request scan, the FIFO stage + commit scatters, the
scoped-f64 resource solve (host round-trip), host batch-slot sampling, the
vmapped local SGD, the scored server round, and an un-jitted eval. This
module fuses the whole round — and ``rounds_per_dispatch`` consecutive
rounds — into ONE jitted XLA executable:

    segment(carry) = lax.scan(round_body, carry, length=k)

with every per-round random draw moved on device (threefry):

  * arrival counts: Binomial(E_u, p_ac) as E_u summed Bernoulli draws —
    exact Binomial, replacing ``np.random.Generator.binomial``;
  * request samples: the Gumbel-trick scan body
    (``data/video_caching_stacked._draw_block``) at static warmup=0 — the
    engine refuses a cohort whose request windows are still cold
    (``warmup_deficit`` > 0), which the harness's initial fill guarantees
    never happens;
  * channel shadowing: Normal(0, 8 dB) per client;
  * local-SGD batch slots: uniform over each client's live FIFO window
    (the device twin of ``StackedOnlineBuffer.sample_slots``).

Per-round randomness is keyed ``fold_in(base_key, t)`` with t the ABSOLUTE
round index carried through the scan, so segmentation is invisible to the
trajectory: rounds [0, 2k) as one segment, two segments of k, or a resume
from a RunState snapshot at any segment boundary are bit-identical
(tests/test_round_fused.py).

The resource solve inlines ``core/resource_stacked.make_solver_core``,
batched over all (rounds x U) lanes of the segment AHEAD of the scan
(``_solve_segment``): the solve depends only on the per-round keys, never
on the model/buffer carry, and the solver is lane-elementwise (its masks
and init-point sweep never reduce across lanes), so hoisting is bit-exact
per round while keeping the whole segment one executable. Leaving it in
the scan body let XLA:CPU re-fuse the SCA chain into its SGD/aggregation
consumers and cost ~1.6x on the full round at U=256. Backends:

  * ``resource_backend="f32"``: the log-domain SNR reformulation — the whole
    program is f32/int32, compiles without ``enable_x64`` and can run on
    TPU/GPU. Non-finite decisions on feasible lanes (knife-edge configs) are
    flagged per round and surfaced as ``ResourceSolveError`` by the caller
    via ``FusedEngine.check_outputs``.
  * ``resource_backend="x64"``: the segment is traced/AOT-compiled under
    scoped ``enable_x64`` with the solve in f64 — the CPU parity oracle,
    bit-exact against the multi-dispatch engine when both are driven with
    the same device draws (the replay test).

Each layer of the round runs under a ``jax.named_scope`` (``request_gen``,
``fifo_commit``, ``local_sgd``, ``aggregate``, ``eval``, ``resource_solve``),
placed in the traced bodies that the dispatch round shares, so the ops of
both rounds name their layer in their ``op_name`` metadata and a profiler
trace can be split by layer. (The dispatch round evaluates eagerly, op by
op; eager ops take no scope from their caller.)

``FusedEngine`` owns one AOT-compiled executable per distinct segment
length (``compiled_text`` exposes its optimized HLO for
``launch/hlo_analysis.dispatch_report``); ``repro/harness/experiments.py`` glues it
to the harness state + RunState checkpoints and ``benchmarks/bench_online.py``
times it and gates the single-dispatch claim.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig
from repro.core.buffer_stacked import BufState, _commit_impl, _stage_impl
from repro.core.client import make_local_train_body
from repro.core.osafl import make_stacked_round_body
from repro.core.resource import NetworkConfig, pathloss_linear
from repro.core.resource_stacked import (ClientSystemBatch,
                                         RESOURCE_BACKENDS,
                                         ResourceSolveError, make_solver_core)
from repro.data.video_caching_stacked import (StreamConsts, StreamState,
                                              _draw_block, warmup_deficit)
from repro.models.small import small_loss

# decorrelates the fused per-round key chain from every other PRNGKey(seed)
# consumer (model init, the request stream's own 0x726571 lineage)
ROUND_KEY_TAG = 0x0f5afe


def fused_base_key(seed: int) -> jnp.ndarray:
    """Root of the fused engine's per-round threefry chain for a run seed."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), ROUND_KEY_TAG)


def round_keys(base_key, t):
    """(k_arrivals, k_channel, k_slots) for absolute round ``t`` — shared by
    the in-scan round body and the multi-dispatch replay in the parity
    tests, so both paths consume identical device draws."""
    k = jax.random.fold_in(base_key, t)
    k_arr, k_chan, k_slots = jax.random.split(k, 3)
    return k_arr, k_chan, k_slots


def draw_counts(key, p_ac, width: int) -> jnp.ndarray:
    """Exact Binomial(width, p_ac[u]) arrival counts as ``width`` summed
    Bernoulli draws (the device replacement for
    ``data/online.binomial_arrivals_batched``)."""
    u = jax.random.uniform(key, (p_ac.shape[0], width), jnp.float32)
    return jnp.sum(u < p_ac[:, None], axis=1).astype(jnp.int32)


def draw_shadowing_db(key, num_users: int,
                      shadow_sigma_db: float = 8.0) -> jnp.ndarray:
    """Per-client log-normal shadowing draw in dB (the device twin of
    ``resource_stacked.sample_channels``' normal draw)."""
    return jax.random.normal(key, (num_users,), jnp.float32) * shadow_sigma_db


def draw_slots(key, size, head, cap, sample_shape: tuple) -> jnp.ndarray:
    """(U, *sample_shape) storage slots uniform over each client's live FIFO
    window — ``StackedOnlineBuffer.sample_slots`` with the host Generator
    replaced by a threefry uniform (empty buffers fall back to slot head)."""
    U = size.shape[0]
    lead = (U,) + (1,) * len(sample_shape)
    sz = jnp.maximum(size, 1).reshape(lead)
    u = jax.random.uniform(key, (U,) + tuple(sample_shape), jnp.float32)
    j = jnp.minimum(jnp.floor(u * sz).astype(jnp.int32), sz - 1)
    return (head.reshape(lead) + j) % cap.reshape(lead)


class FusedCarry(NamedTuple):
    """Everything one round mutates, as one device pytree: the server state
    (flat weights, (U, N) contribution buffer, participation flags, stale
    score carry), the FIFO buffer, the request-stream Markov state, and the
    absolute round index that keys the per-round randomness."""
    w: jnp.ndarray
    d_buffer: jnp.ndarray
    participated: jnp.ndarray
    lam_prev: jnp.ndarray
    buf: BufState
    stream: StreamState
    t: jnp.ndarray              # () int32 absolute round index


class FusedEngine:
    """Compiles and runs single-dispatch segments of the online OSAFL round.

    Construction takes only core/data-layer objects (no harness types);
    ``repro/harness/experiments.py`` adapts its setup namespace. Restrictions: the
    fused body is the OSAFL scored round over the stacked request stream, so
    ``fl.algorithm`` must be ``"osafl"`` and ``fl.request_backend``
    ``"stacked"``; the FIFO buffer must be unsharded (the segment is one
    single-device program)."""

    def __init__(self, *, fl: FLConfig, codec, model: str,
                 consts: StreamConsts, topk: int, dataset: int,
                 arrivals: int, batch: int, p_ac, sysb: ClientSystemBatch,
                 net: NetworkConfig, n_params: int, test_batch, alphas,
                 sketch_key, seed: int, use_resource_opt: bool = True,
                 resource_backend: str = "f32"):
        if fl.algorithm != "osafl":
            raise ValueError(
                "the fused round implements the OSAFL scored round only "
                f"(got algorithm={fl.algorithm!r}); run other algorithms "
                "with round_backend='dispatch'")
        if fl.request_backend != "stacked":
            raise ValueError(
                "the fused round draws requests with the stacked Gumbel "
                "sampler; set request_backend='stacked' "
                f"(got {fl.request_backend!r})")
        if fl.cohort_size:
            raise ValueError(
                "the fused round is dense-only: its carry bakes slot index "
                "== user id into one static program, which the sparse "
                "slot-pool engine (core/cohort.py) breaks by design; run "
                "cohort_size>0 with round_backend='dispatch' (a slot-"
                "indexed fused carry is a scoped ROADMAP follow-up)")
        if resource_backend not in RESOURCE_BACKENDS:
            raise ValueError(f"unknown resource backend {resource_backend!r} "
                             f"(expected one of {RESOURCE_BACKENDS})")
        self.fl = fl
        self.codec = codec
        self.model = model
        self.consts = consts
        self.topk = int(topk)
        self.dataset = int(dataset)
        self.arrivals = int(arrivals)
        self.batch = int(batch)
        self.use_resource_opt = bool(use_resource_opt)
        self.resource_backend = resource_backend
        self.p_ac = jnp.asarray(p_ac, jnp.float32)
        self.test_batch = jax.tree.map(jnp.asarray, test_batch)
        self.alphas = jnp.asarray(alphas, jnp.float32)
        self.sketch_key = jnp.asarray(sketch_key)
        self.base_key = fused_base_key(seed)
        self.net = net
        self.n_params = int(n_params)
        # the solve's constant columns live in the solve dtype up front so
        # the f32 program never touches f64 and the x64 trace never upcasts
        sdt = np.float64 if resource_backend == "x64" else np.float32
        self._sys_cols = tuple(
            np.asarray(a, sdt)
            for a in (sysb.c, sysb.s, sysb.f_max, sysb.p_max, sysb.e_bd))
        self._xi = np.asarray(pathloss_linear(sysb.distance), sdt)
        self._n_params_c = sdt(n_params)
        self._round_body = self._make_round_body()
        self._compiled_cache: dict = {}

    # -- the fused round -----------------------------------------------------
    def _make_round_body(self):
        fl = self.fl
        codec = self.codec
        model = self.model
        consts, topk, dataset = self.consts, self.topk, self.dataset
        arrivals, batch = self.arrivals, self.batch
        p_ac, alphas, sketch_key = self.p_ac, self.alphas, self.sketch_key
        base_key, test_batch = self.base_key, self.test_batch
        grad_fn = jax.grad(lambda p, b: small_loss(p, b, model)[0])
        one_client = make_local_train_body(grad_fn, fl.local_lr,
                                           fl.kappa_max, prox_mu=0.0)
        local = jax.vmap(one_client, in_axes=(None, 0, 0))
        srv_round = make_stacked_round_body(fl)

        def round_body(carry: FusedCarry, solved):
            kap_t, bad_solve = solved
            t = carry.t
            k_arr, _, k_slots = round_keys(base_key, t)
            # 1. arrivals: on-device Binomial counts + Gumbel-trick samples
            with jax.named_scope("request_gen"):
                counts = draw_counts(k_arr, p_ac, arrivals)
                stream, xs, ys = _draw_block(consts, carry.stream, counts,
                                             width=arrivals, warmup=0,
                                             dataset=dataset, topk=topk)
            # 2. FIFO commit (the round-boundary scatter)
            buf = _commit_impl(_stage_impl(carry.buf, xs, ys, counts))
            # 3. this round's resource decisions, solved ahead of the scan
            # (_solve_segment) — the solve only depends on the round keys,
            # and keeping its graph out of the scan body stops XLA:CPU from
            # re-fusing the whole SCA chain into the SGD consumers (~1.6x
            # on the full round at U=256)
            kappas = kap_t.astype(jnp.int32)
            active = kappas >= 1
            # 4. masked kappa_u-step local SGD over the whole cohort
            with jax.named_scope("local_sgd"):
                slots = draw_slots(k_slots, buf.size, buf.head, buf.cap,
                                   (fl.kappa_max, batch))
                uu = jnp.arange(p_ac.shape[0], dtype=jnp.int32
                                ).reshape(-1, 1, 1)
                batches = {"x": buf.x[uu, slots], "y": buf.y[uu, slots]}
                d, _ = local(codec.unflatten(carry.w), batches, kappas)
                upd = codec.flatten_stacked(d)
            # 5. eq. 19-21 scored aggregation
            w, dbuf, part, lam_use, lam = srv_round(
                carry.w, carry.d_buffer, carry.participated, carry.lam_prev,
                upd, active, alphas, sketch_key)
            # 6. eval (inside the scan: per-round history, still 1 dispatch)
            with jax.named_scope("eval"):
                loss, m = small_loss(codec.unflatten(w), test_batch, model)
            out = {"test_loss": loss.astype(jnp.float32),
                   "test_acc": m["accuracy"].astype(jnp.float32),
                   "participants": jnp.sum(active).astype(jnp.int32),
                   "lam_use": lam_use.astype(jnp.float32),
                   "bad_solve": bad_solve}
            new_carry = FusedCarry(w, dbuf, part, lam, buf, stream,
                                   t + jnp.int32(1))
            return new_carry, out

        return round_body

    @jax.named_scope("resource_solve")
    def _solve_segment(self, ts):
        """All ``len(ts)`` rounds' channel draws + resource solves, batched
        over (rounds x U) lanes: ``(kappas (k, U) in the solve dtype,
        bad_solve (k,) bool)``. The solve depends only on the per-round keys
        (never on the model/buffer carry), so the segment program runs it
        once ahead of the ``lax.scan`` — inside the same executable, but out
        of the scan body, where XLA:CPU would otherwise re-fuse the SCA
        chain into each of its SGD/aggregation consumers."""
        U = self.p_ac.shape[0]
        sdt = jnp.float64 if self.resource_backend == "x64" else jnp.float32
        if not self.use_resource_opt:
            k = ts.shape[0]
            return (jnp.full((k, U), self.fl.kappa_max, sdt),
                    jnp.zeros((k,), bool))
        base_key = self.base_key
        k_chans = jax.vmap(lambda t: round_keys(base_key, t)[1])(ts)
        gammas = jax.vmap(
            lambda kc: 10.0 ** (draw_shadowing_db(kc, U).astype(sdt)
                                / 10.0))(k_chans)
        k = ts.shape[0]
        solve = make_solver_core(self.net, self.resource_backend)
        tiled = tuple(jnp.tile(jnp.asarray(c), k) for c in self._sys_cols)
        kap, f, p, feas, _, _ = solve(*tiled, jnp.tile(
            jnp.asarray(self._xi), k), gammas.reshape(-1), self._n_params_c)
        bad = feas & ~(jnp.isfinite(kap) & jnp.isfinite(f)
                       & jnp.isfinite(p))
        return kap.reshape(k, U), jnp.any(bad.reshape(k, U), axis=1)

    def _make_segment(self, length: int):
        body = self._round_body
        solve_segment = self._solve_segment

        def segment(carry):
            ts = carry.t + jnp.arange(length, dtype=jnp.int32)
            return jax.lax.scan(body, carry, solve_segment(ts))

        return segment

    def _compiled(self, carry: FusedCarry, length: int):
        if length not in self._compiled_cache:
            seg = jax.jit(self._make_segment(length))
            if self.resource_backend == "x64":
                # scoped-x64 trace: the solve's f64 closure constants stay
                # f64; every carry/draw aval is explicitly typed so the
                # executable's signature is identical to the f32 program's
                with jax.enable_x64(True):
                    compiled = seg.lower(carry).compile()
            else:
                compiled = seg.lower(carry).compile()
            self._compiled_cache[length] = compiled
        return self._compiled_cache[length]

    # -- public API ----------------------------------------------------------
    def init_carry(self, server, sbuf, rstream, t: int) -> FusedCarry:
        """Lift the harness's mutable state into a device carry at absolute
        round ``t``. Refuses cold request windows (the in-scan draw runs at
        static warmup=0) and sharded buffers (one single-device program)."""
        if sbuf.mesh is not None:
            raise ValueError("the fused round does not support mesh-sharded "
                             "buffers; use round_backend='dispatch'")
        deficit = warmup_deficit(rstream.state, self.dataset)
        if deficit:
            raise ValueError(
                f"fused rounds need a warm cohort window (worst-case warmup "
                f"deficit is {deficit}); fill the FIFO buffers before "
                "entering the fused engine")
        return FusedCarry(
            w=server.w, d_buffer=server.d_buffer,
            participated=jnp.asarray(server.participated),
            lam_prev=server._lam_prev,
            buf=sbuf.state, stream=rstream.state,
            t=jnp.asarray(t, jnp.int32))

    def run_segment(self, carry: FusedCarry, length: int):
        """Execute ``length`` rounds as one device dispatch. Returns the new
        carry and a dict of per-round output columns (length-leading)."""
        if length < 1:
            raise ValueError(f"segment length must be >= 1, got {length}")
        with jax.profiler.TraceAnnotation("osafl.dispatch"):
            return self._compiled(carry, int(length))(carry)

    @staticmethod
    def check_outputs(outs: dict) -> None:
        """Raise ``ResourceSolveError`` if any round's f32 solve lost a
        feasible lane to non-finite kappa/f/p (knife-edge configs — the
        in-jit counterpart of ``resource_stacked._check_finite``)."""
        bad = np.asarray(outs["bad_solve"])
        if bad.any():
            rounds = np.flatnonzero(bad)
            raise ResourceSolveError(
                "fused resource solve produced non-finite kappa/f/p on "
                f"feasible clients in segment round(s) {rounds.tolist()}; "
                "for tight-deadline/knife-edge configurations run "
                "resource_backend='x64'")

    def write_back(self, carry: FusedCarry, outs: dict, server, sbuf,
                   rstream) -> None:
        """Push a segment-final carry back into the harness's mutable
        objects so checkpointing/eval see exactly the state the dispatch
        engine would hold after the same rounds."""
        server.w = carry.w
        server.d_buffer = carry.d_buffer
        server.participated = carry.participated
        server._lam_prev = carry.lam_prev
        server.last_scores = np.asarray(outs["lam_use"][-1])
        sbuf.state = carry.buf
        rstream.state = carry.stream

    def compiled_text(self, length: int) -> str:
        """Optimized HLO of the compiled ``length``-round segment (for
        ``launch/hlo_analysis.dispatch_report``); the segment must have been
        run (or compiled) first."""
        if length not in self._compiled_cache:
            raise ValueError(f"no compiled segment of length {length}; call "
                             "run_segment first")
        return self._compiled_cache[length].as_text()
