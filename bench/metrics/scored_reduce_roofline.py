"""scored_reduce_roofline (%): the least time the score kernel could take,
over the time it took. It reads the (U, N) float32 update plane and the (N,)
mean once (U N 4 + N 4 bytes) and does 4 U N + 2 N FLOPs, so HBM bandwidth
bounds it: the least time is the bytes over the chip's HBM bandwidth."""


def read(t):
    U, N = t.plane_rows, t.n_params
    bytes_ = 4.0 * (U * N + N)
    floor_s = max(bytes_ / t.peaks["hbm_bytes_per_s"],
                  (4.0 * U * N + 2.0 * N) / t.peaks["bf16_flops"])
    times = [e[1] / 1e9 for ops in t.ops
             for e in t.tz.kernel_events(ops, U, N) if t.lo <= e[0] <= t.hi]
    if not times:
        return None
    return 100.0 * floor_s / (sum(times) / len(times))
