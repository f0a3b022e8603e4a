"""Plain reference of the paper's FCN (arXiv:2408.05886, Appendix D):
3168 -> 1024 -> 512 -> 100, ReLU between layers, on Dataset-1's feature rows.

``init`` makes weights in the layout the system under test takes (a dict of
layers, each ``{"w": (din, dout), "b": (dout,)}``), He-normal weights and zero
biases; layer i's weights come from the first of one split of the i-th of
three splits of the key. ``forward`` is plain ``jax.numpy``. ``flops_per_sample`` counts the
multiply-adds of the dense layers as 2 FLOPs each; bias adds and activations
are left out.
"""
import jax
import jax.numpy as jnp

WIDTHS = (3168, 1024, 512, 100)
LAYERS = ("l1", "l2", "l3")


def init(key):
    keys = jax.random.split(key, len(LAYERS))
    return {name: {"w": (2.0 / din) ** 0.5 * jax.random.normal(
                       jax.random.split(k, 1)[0], (din, dout), jnp.float32),
                   "b": jnp.zeros((dout,), jnp.float32)}
            for name, k, din, dout in zip(LAYERS, keys, WIDTHS[:-1],
                                          WIDTHS[1:])}


def forward(params, x):
    h = x
    for i, name in enumerate(LAYERS):
        h = h @ params[name]["w"] + params[name]["b"]
        if i < len(LAYERS) - 1:
            h = jax.nn.relu(h)
    return h


def flops_per_sample() -> dict:
    """Forward: 2 din dout a layer. Training adds the weight gradient of
    every layer and the input gradient of every layer but the first, whose
    input is data."""
    mats = [din * dout for din, dout in zip(WIDTHS[:-1], WIDTHS[1:])]
    fwd = 2 * sum(mats)
    return {"forward": fwd, "train": fwd + 2 * sum(mats) + 2 * sum(mats[1:])}
