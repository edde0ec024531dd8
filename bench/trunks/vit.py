"""The ViT detector: the repository's detector on a plain ViT trunk
(Dosovitskiy et al., arXiv:2010.11929).

``forward`` is the plain reference in float32 at ``highest`` matmul
precision: patchify (rows, columns, then each patch's pixels row-major
with the channel last), linear patch embedding, learned position
embedding, pre-norm encoder blocks (layer norm eps 1e-6, multi-head
attention without biases, GELU MLP in its tanh form as in the ViT
reference implementation), final layer norm, and a per-token linear head
of 5 outputs (objectness and box).  It reads the benchmark's own weights
(``bench.model.make_weights``), never the program's, and the tree's names
(``trunk``, ``layers``, ...).

The counts come from shapes alone.  A matrix product of (m, k) by (k, n)
counts 2*m*k*n operations.  Only the products are counted: layer norms,
softmax and GELU add well under 2% at the served widths
(``tests/bench/test_bench_trunks.py`` holds the count against XLA's own
cost analysis).  The program's own ``core.latency.detector_flops`` counts
the MLP twice (``2 * d * d_ff * 2`` is doubled again), which overstates
the ViT-B/32 detector by 1.54x; a roofline or MFU built on it could read
over 100%.
"""
from __future__ import annotations

import functools

import numpy as np

LN_EPS = 1e-6


def _layernorm(x, scale, bias):
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def _gelu(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                      * (x + 0.044715 * x ** 3)))


def forward(params, canvases, records, arch: dict, dtype="float32"):
    """One invocation's (B, M, N, 3) canvases -> (B, side, side, 5) raw
    head outputs, one canvas at a time so that a batch of any size fits.

    ``records`` are the invocation's placements (``reference.stitch``'s);
    every token attends to every other, so the ViT reads none of them.
    ``dtype`` is the precision every matrix product's operands are
    rounded to (``float32``, or a lower one for a control); the products
    accumulate in float32 at ``highest`` precision."""
    import jax.numpy as jnp

    fn = _jitted(tuple(sorted(arch.items())), dtype)
    return np.concatenate([np.asarray(fn(params, jnp.asarray(c[None])))
                           for c in np.asarray(canvases)])


@functools.lru_cache(maxsize=None)
def _jitted(arch_items: tuple, dtype):
    import jax

    arch = dict(arch_items)
    return jax.jit(lambda p, x: _canvas_forward(p, x, arch, dtype))


def _canvas_forward(params, canvases, arch: dict, dtype):
    """(b, M, N, 3) canvases -> (b, side, side, 5) raw head outputs."""
    import jax
    import jax.numpy as jnp

    def q(x):
        return x.astype(dtype).astype(jnp.float32)

    def mm(eq, a, b):
        return jnp.einsum(eq, q(a), q(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), t)
    tp = f32(params["trunk"])
    head = f32(params["det_head"])
    p = arch["patch"]
    b, m, n, c = canvases.shape
    x = canvases.astype(jnp.float32).reshape(b, m // p, p, n // p, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (m // p) * (n // p),
                                              p * p * c)
    x = mm("bsi,id->bsd", x, tp["patch_embed"]["kernel"]) \
        + tp["patch_embed"]["bias"]
    x = x + tp["pos_embed"]
    n_heads = arch["n_heads"]
    head_dim = arch["d_model"] // n_heads
    layers = tp["layers"]
    for i in range(arch["n_layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], layers)
        h = _layernorm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
        qh = mm("bsd,dhk->bshk", h, lp["attn"]["wq"])
        kh = mm("bsd,dhk->bshk", h, lp["attn"]["wk"])
        vh = mm("bsd,dhk->bshk", h, lp["attn"]["wv"])
        s = mm("bqhk,bthk->bhqt", qh, kh) / np.sqrt(head_dim)
        a = jax.nn.softmax(s, axis=-1)
        ctx = mm("bhqt,bthk->bqhk", a, vh)
        x = x + mm("bqhk,hkd->bqd", ctx, lp["attn"]["wo"])
        h = _layernorm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
        h = _gelu(mm("bsd,df->bsf", h, lp["mlp"]["fc1"]["kernel"])
                  + lp["mlp"]["fc1"]["bias"])
        x = x + mm("bsf,fd->bsd", h, lp["mlp"]["fc2"]["kernel"]) \
            + lp["mlp"]["fc2"]["bias"]
    x = _layernorm(x, tp["ln_f"]["scale"], tp["ln_f"]["bias"])
    raw = mm("bsd,do->bso", x, head["kernel"]) + head["bias"]
    return raw.reshape(b, m // p, n // p, 5)


def live_mask(records, arch: dict) -> np.ndarray:
    """(B, side, side) bool: the tokens that the valid placements of
    ``records`` overlap, a token being the ``patch``-sized square at its
    grid position.  A trunk that skips tokens counts and computes over
    these."""
    p = arch["patch"]
    side = arch["canvas"] // p
    records = np.asarray(records)
    mask = np.zeros((records.shape[0], side, side), bool)
    for bi, recs in enumerate(records.tolist()):
        for valid, _slot, x, y, w, h in recs:
            if valid > 0:
                mask[bi, y // p:-(-(y + h) // p),
                     x // p:-(-(x + w) // p)] = True
    return mask


def flops(arch: dict, rec) -> float:
    """Forward operations of one invocation: every token of each of its
    ``rec.canvases`` canvases runs the whole trunk."""
    side = arch["canvas"] // arch["patch"]
    s = side * side
    d, d_ff = arch["d_model"], arch["d_ff"]
    embed = 2 * s * (3 * arch["patch"] ** 2) * d
    proj = 2 * s * d * d * 4                 # q, k, v, out
    attn = 2 * s * s * d * 2                 # scores and context
    mlp = 2 * s * d * d_ff * 2
    head = 2 * s * d * 5
    return rec.canvases * float(embed + arch["n_layers"]
                                * (proj + attn + mlp) + head)


def params(arch: dict) -> int:
    side = arch["canvas"] // arch["patch"]
    d, d_ff = arch["d_model"], arch["d_ff"]
    per_layer = 4 * d * d + 2 * d * d_ff + d_ff + d + 4 * d
    return (arch["n_layers"] * per_layer + 3 * arch["patch"] ** 2 * d + d
            + side * side * d + 2 * d + 5 * d + 5)


def bytes(arch: dict, rec, param_bytes: int = 2) -> float:
    """Least HBM traffic of one invocation: weights once, the float32
    canvases in, the float32 (objectness, box) grids out."""
    side = arch["canvas"] // arch["patch"]
    canvases = rec.canvases * arch["canvas"] ** 2 * 3 * 4
    outputs = rec.canvases * side * side * 5 * 4
    return float(params(arch) * param_bytes + canvases + outputs)
