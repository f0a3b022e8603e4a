"""Plain reference of the paper's 3-layer LSTM (arXiv:2408.05886, Appendix D)
on Dataset-2: the last 10 request ids, embedded in 64 dims, through LSTM
layers of 128 units, and a dense head over 100 classes from the last step.

``init`` makes weights in the layout the system under test takes (``embed``
(100, 64); each layer ``{"wx": (din, 512), "wh": (128, 512), "b": (512,)}``
with the gates in the order i, f, g, o; ``head`` ``{"w", "b"}``), from five
splits of the key: the embedding at 0.02, each layer's two matrices from
two splits of its key at 1/sqrt(din) and 1/sqrt(128), the head from one split
of its key at sqrt(2/128); biases 0.
``forward`` is plain ``jax.numpy`` with a Python loop over the time steps.
``flops_per_sample`` counts the matrix products as 2 FLOPs a multiply-add.
"""
import jax
import jax.numpy as jnp

CLASSES, EMBED, HIDDEN, SEQ = 100, 64, 128, 10
LAYERS = ("l1", "l2", "l3")


def init(key):
    ks = jax.random.split(key, 5)
    params = {"embed": 0.02 * jax.random.normal(ks[0], (CLASSES, EMBED),
                                                jnp.float32)}
    din = EMBED
    for name, k in zip(LAYERS, ks[1:4]):
        kx, kh = jax.random.split(k)
        params[name] = {
            "wx": jax.random.normal(kx, (din, 4 * HIDDEN), jnp.float32)
            * (1.0 / din) ** 0.5,
            "wh": jax.random.normal(kh, (HIDDEN, 4 * HIDDEN), jnp.float32)
            * (1.0 / HIDDEN) ** 0.5,
            "b": jnp.zeros((4 * HIDDEN,), jnp.float32)}
        din = HIDDEN
    params["head"] = {"w": (2.0 / HIDDEN) ** 0.5 * jax.random.normal(
                          jax.random.split(ks[4], 1)[0], (HIDDEN, CLASSES),
                          jnp.float32),
                      "b": jnp.zeros((CLASSES,), jnp.float32)}
    return params


def forward(params, x):
    h = params["embed"][x]                       # (B, SEQ, EMBED)
    for name in LAYERS:
        p = params[name]
        hs = []
        ht = jnp.zeros((h.shape[0], HIDDEN), h.dtype)
        c = jnp.zeros_like(ht)
        for t in range(h.shape[1]):
            z = h[:, t] @ p["wx"] + ht @ p["wh"] + p["b"]
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            ht = jax.nn.sigmoid(o) * jnp.tanh(c)
            hs.append(ht)
        h = jnp.stack(hs, axis=1)
    return h[:, -1] @ params["head"]["w"] + params["head"]["b"]


def flops_per_sample() -> dict:
    """Forward: per layer and step 2 (din + HIDDEN) 4 HIDDEN, and the head
    once. Training adds the weight gradients (as much again) and the input
    gradients (as much again: every layer's input needs one, the first
    layer's for the embedding's gradient, the head's for the last step)."""
    per_step = sum(2 * (din + HIDDEN) * 4 * HIDDEN
                   for din in (EMBED, HIDDEN, HIDDEN))
    fwd = SEQ * per_step + 2 * HIDDEN * CLASSES
    return {"forward": fwd, "train": 3 * fwd}
