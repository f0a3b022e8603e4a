"""host_draws_ms (ms): the host's draws of a round in the host-driven round,
the mean of the run's own ``request_gen_s`` over the traced call's measured
rounds. The run times, from the start of its per-round draws to the end of
the synced request draw: the participant sample, slot admission, the arrival
counts and the request generation (``_draw_round_inputs``)."""


def read(t):
    if not t.request_gen_s:
        return None
    return 1000.0 * sum(t.request_gen_s) / len(t.request_gen_s)
