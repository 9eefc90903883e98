"""Plain float32 reference of the served models, and its fp8 control.

A decoder of pre-norm blocks, written from the published description of
the models the configurations name (InternLM2, Mistral): RMSNorm,
grouped-query attention with rotary positions (rotate-half, inverse
frequencies ``theta ** (-i / (dh / 2))``), causal softmax, a SwiGLU MLP
and an untied LM head.  It imports nothing of the program under test
and reads only the benchmark's own weights, keyed by their path in the
parameter tree (``layers/3/mixer/wq``).  Activations are float32 and
every product is exact to float32: a float32 input times a bfloat16
weight is taken as three bfloat16 parts of the input, each product
exact, summed in float32 (what ``Precision.HIGHEST`` does, without a
float32 copy of the weight), and attention runs at ``HIGHEST``.  One
layer per call, one sequence at a time, so that it fits beside the
weights once the engine is freed.

The control is the same forward computed in the precision below the
configuration's bfloat16: every linear layer in fp8 (e4m3) with its
weights scaled per output channel and its inputs per token, products
accumulated in float32.  Attention and norms stay float32.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _bucket(n: int, step: int) -> int:
    return -(-n // step) * step


def _fp8(x: jax.Array, axis: int):
    """``x`` rounded to e4m3, each slice along ``axis`` scaled to span
    the format's range: (the e4m3 values as bfloat16, which holds them
    exactly, and the float32 scales)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1, scale)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    return q, scale.astype(jnp.float32)


def _split3(x: jax.Array):
    """Three bfloat16 parts whose sum is ``x`` to float32 precision."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    return hi, mid, (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _linear(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """x [T, n_in] float32 @ w [n_in, n_out], exact to float32; in fp8
    (inputs scaled per token, weights per output) when asked."""
    if fp8:
        (xq, sx), (wq, sw) = _fp8(x, axis=1), _fp8(w, axis=0)
        return jnp.dot(xq, wq, preferred_element_type=jnp.float32) * sx * sw
    if w.dtype == jnp.float32:
        return jnp.dot(x, w, precision=HIGHEST)
    w = w.astype(jnp.bfloat16)
    return sum(jnp.dot(p, w, preferred_element_type=jnp.float32)
               for p in _split3(x))


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [T, H, dh], token t at position t."""
    t, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta", "fp8"))
def _block(h, p, *, heads, kv_heads, eps, theta, fp8):
    t, d = h.shape
    dh = p["wq"].shape[-1]
    x = _rms(h, p["ln1"], eps)
    q = _linear(x, p["wq"].reshape(d, -1), fp8).reshape(t, heads, dh)
    k = _linear(x, p["wk"].reshape(d, -1), fp8).reshape(t, kv_heads, dh)
    v = _linear(x, p["wv"].reshape(d, -1), fp8).reshape(t, kv_heads, dh)
    q, k = _rope(q, theta), _rope(k, theta)
    g = heads // kv_heads
    q = q.reshape(t, kv_heads, g, dh)
    s = jnp.einsum("qjgd,kjd->jgqk", q, k, precision=HIGHEST) * dh ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("jgqk,kjd->qjgd", a, v, precision=HIGHEST)
    h = h + _linear(o.reshape(t, heads * dh),
                    p["wo"].reshape(heads * dh, d), fp8)
    x = _rms(h, p["ln2"], eps)
    u = jax.nn.silu(_linear(x, p["w_gate"], fp8)) * _linear(x, p["w_up"],
                                                            fp8)
    return h + _linear(u, p["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(h, rows, final_ln, head, *, eps, fp8):
    return _linear(_rms(h[rows], final_ln, eps), head, fp8)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def logits_at(w: Dict[str, jax.Array], model: Dict, tokens: Sequence[int],
              rows: Sequence[int], fp8: bool = False) -> jax.Array:
    """Logits [len(rows), vocab] after ``tokens[r]`` for each ``r`` in
    ``rows``.  The sequence is padded at its end to a multiple of 512
    (causal: padding changes no earlier row) and the rows to a multiple
    of 128, so that few shapes compile."""
    n = len(tokens)
    tok = np.zeros(_bucket(n, 512), np.int32)
    tok[:n] = tokens
    r = np.zeros(_bucket(len(rows), 128), np.int32)
    r[:len(rows)] = rows
    h = _embed(w["embed/table"], jnp.asarray(tok))
    for i in range(model["num_layers"]):
        pre = f"layers/{i}/"
        p = {"ln1": w[pre + "ln1/scale"], "ln2": w[pre + "ln2/scale"],
             **{k: w[pre + "mixer/" + k] for k in ("wq", "wk", "wv", "wo")},
             **{k: w[pre + "ffn/" + k] for k in ("w_gate", "w_up",
                                                 "w_down")}}
        h = _block(h, p, heads=model["num_heads"],
                   kv_heads=model["num_kv_heads"], eps=model["norm_eps"],
                   theta=model["rope_theta"], fp8=fp8)
    out = _head(h, jnp.asarray(r), w["final_ln/scale"], w["embed/head"],
                eps=model["norm_eps"], fp8=fp8)
    return out[:len(rows)]


@jax.jit
def _gaps(ref, served):
    top = ref.max(axis=-1)
    return top - jnp.take_along_axis(ref, served[:, None], axis=1)[:, 0]


def served_gaps(w: Dict[str, jax.Array], model: Dict, prompt: List[int],
                out: List[int], control: bool = False) -> Dict[str, np.ndarray]:
    """For each served token: how far its reference logit lies below the
    reference's best (``gap``, 0 where it is the argmax).  With
    ``control``, also the gap of the token the fp8 forward puts first at
    the same position (``control_gap``)."""
    seq = list(prompt) + list(out[:-1])
    rows = list(range(len(prompt) - 1, len(seq)))
    ref = logits_at(w, model, seq, rows)
    served = jnp.asarray(np.asarray(out, np.int32))
    res = {"gap": np.asarray(_gaps(ref, served))}
    if control:
        ctl = logits_at(w, model, seq, rows, fp8=True)
        res["control_gap"] = np.asarray(
            _gaps(ref, jnp.argmax(ctl, axis=-1).astype(jnp.int32)))
    return res
