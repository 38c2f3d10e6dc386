"""The plain float32 reference: what every architecture's forward pass is
built from, and the interface the correctness check calls.

Written from the published description of each architecture, not from the
program under test, whose code it does not import.  The layer stack lives in
the configuration's module under ``perfbench/arch/``; this file keeps the
generic parts: the matrix product in the reference's precision or the
control's, RMSNorm, rotary position embedding, blocked causal attention,
padding of the sequence, and the per-tensor int8 scales.

It reads the configuration file and the weight tree that the benchmark made
from the seed.  Every matrix product runs at ``Precision.HIGHEST``, since a
TPU otherwise rounds float32 operands to bfloat16.  The weights stay in their
served dtype and are upcast one layer at a time, so the reference fits on the
chip beside them; attention runs in blocks of queries.

``control`` computes the same model in a lower precision, as the control of
the correctness check:

* ``"int8"``: every weight matrix rounded through symmetric int8 with one
  scale per weight tensor (the scheme of the program's own ``quantize``
  path) and products in bfloat16, as that path computes;
* ``"fp8"``: both operands of every product rounded to float8 e4m3, weights
  with one scale per tensor and activations with one per row.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
#: Queries per attention block: bounds the score block to
#: heads x Q_BLOCK x length floats.
Q_BLOCK = 512
#: Sequences are padded to a multiple of this, so that a few programs serve
#: every length (padding after the last real token changes nothing before it
#: under the causal mask).
PAD_TO = 512

_F8 = jnp.float8_e4m3fn
_F8_MAX = float(jnp.finfo(_F8).max)


def _q_int8(w, scale):
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _q_fp8(x, axis=None):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / _F8_MAX
    return (x / s).astype(_F8).astype(jnp.float32) * s


class Matmul:
    """``x @ w`` over the given einsum, in the reference's precision or the
    control's."""

    def __init__(self, control=None, scales=None):
        self.control = control
        self.scales = scales or {}

    def weight(self, name, w):
        w = w.astype(jnp.float32)
        if self.control == "int8":
            return _q_int8(w, self.scales[name])
        if self.control == "fp8":
            return _q_fp8(w)
        return w

    def __call__(self, spec, x, w):
        if self.control == "int8":
            return jnp.einsum(spec, x.astype(jnp.bfloat16),
                              w.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        if self.control == "fp8":
            x = _q_fp8(x, axis=-1)
        return jnp.einsum(spec, x, w, precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: (T, n, hd); rotate-half rotary embedding at positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v, scale, mm, window=None):
    """Causal grouped-query attention; q: (T, H, hd), k/v: (T, K, hd).  With
    ``window``, a query sees only itself and the ``window - 1`` keys before
    it."""
    T, H, hd = q.shape
    K = k.shape[1]
    qg = q.reshape(T // Q_BLOCK, Q_BLOCK, K, H // K, hd)
    cols = jnp.arange(T)

    def block(args):
        i, qb = args
        s = mm("qkgd,tkd->kgqt", qb, k) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        keep = cols[None, :] <= rows[:, None]
        if window is not None:
            keep &= cols[None, :] > rows[:, None] - window
        s = jnp.where(keep, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(block, (jnp.arange(T // Q_BLOCK), qg))
    return out.reshape(T, H, hd)


def _flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


class Reference:
    """The reference over one configuration file, one weight tree and the
    configuration's architecture module (``perfbench/arch/``)."""

    def __init__(self, cfg: dict, params: dict, arch):
        self.cfg = cfg
        self.arch = arch
        self.params = _flat(params)
        self._int8_scales = None

    def scales(self, control):
        """Per-tensor int8 scales by path: max |w| / 127 over each whole
        tensor of two or more dimensions (stacked layer tensors, embedding,
        head), as the program's quantize path takes them."""
        if control != "int8":
            return {}
        if self._int8_scales is None:
            self._int8_scales = {
                k: jnp.max(jnp.abs(v.astype(jnp.float32))) / 127.0
                for k, v in self.params.items() if v.ndim >= 2}
        return self._int8_scales

    def logits(self, tokens: np.ndarray, rows: np.ndarray, control=None):
        """Logits (len(rows), vocab) float32 at positions ``rows`` of the
        sequence ``tokens``, on the device."""
        n = len(tokens)
        T = max(PAD_TO, math.ceil(n / PAD_TO) * PAD_TO)
        padded = np.zeros((T,), np.int32)
        padded[:n] = tokens
        return self.arch.reference_logits(
            self.cfg, self.params, jnp.asarray(padded),
            jnp.asarray(rows, jnp.int32), control, self.scales(control))
