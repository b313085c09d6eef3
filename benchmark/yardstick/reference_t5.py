"""Plain reference of the T5 v1.1 encoder (the ``t5xxl`` conditioning tower),
from its published description (Raffel et al. 2020 with the v1.1 changes:
gated-GELU feed-forward, no biases anywhere) and the HF ``T5EncoderModel`` key
layout:

- ``shared.weight``: the token embedding, NOT scaled by √d_model;
- per block, pre-norm residual: RMS norm (``T5LayerNorm``: x · rsqrt(mean x² +
  eps) · weight — no mean subtracted, no bias), then self-attention
  softmax(q kᵀ + bias) v WITHOUT the 1/√d_kv scale (T5 folds it into the
  initialisation), inner width ``num_heads · d_kv`` (need not be ``d_model``);
- the relative-position bias: ``relative_attention_num_buckets`` (32)
  bidirectional buckets — half for keys after the query, half for keys at or
  before it; in each half the first 8 distances exact and the rest log-spaced
  up to ``relative_attention_max_distance`` (128) — looked up in the table of
  block 0 and SHARED by every block (``per_layer_bias``: each block's own
  table, the UMT5 variant);
- feed-forward ``wo(gelu_new(wi_0 x) · wi_1 x)``, ``gelu_new`` the tanh form;
- ``encoder.final_layer_norm`` (RMS) on the way out.

The arithmetic policy is ``reference_sd``'s (imported, not copied). Departures
from the published code, each noted where it is made: the norms' statistics
and the softmax are float32 whatever the mode; padded keys are masked out of
every softmax when a mask is given (what T5's own encoder does with its
padding; ComfyUI's and diffusers' SD3 pipelines hand the tower no mask — the
configuration says which with ``text_t5.attention_mask``). Nothing here
imports the program."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_sd as sd
from .reference_sd import F32


def relative_buckets(n_query: int, n_key: int, num_buckets: int,
                     max_distance: int) -> np.ndarray:
    """(n_query, n_key) int: the bidirectional bucket of key − query."""
    rel = np.arange(n_key)[None, :] - np.arange(n_query)[:, None]
    half = num_buckets // 2
    out = np.where(rel > 0, half, 0)
    n = np.abs(rel)
    exact = half // 2
    large = exact + (np.log(np.maximum(n, 1) / exact)
                     / math.log(max_distance / exact) * (half - exact)).astype(np.int64)
    return out + np.where(n < exact, n, np.minimum(large, half - 1))


def _rms(x, w, eps):
    # float32 statistics whatever the mode (departure: the program's modules
    # do the same; HF computes them in the stream's type)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w.astype(F32)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(p, heads, eps, w, x, bias):
    """One encoder block on (B, S, d_model); ``bias`` (1 or B, H, S, S) holds
    the position bias and −inf at masked keys."""
    a = "layer.0.SelfAttention."
    h = _rms(x, w["layer.0.layer_norm.weight"], eps)
    q, k, v = (sd._linear(p, h, w[f"{a}{m}.weight"]) for m in "qkv")
    b, s, inner = q.shape
    q, k, v = (t.reshape(b, s, heads, inner // heads) for t in (q, k, v))
    mode = "float32" if p == "float32" else "bfloat16"
    logits = sd._ein(mode, "bqhd,bkhd->bhqk", q, k) + bias  # no 1/sqrt(d_kv)
    probs = jax.nn.softmax(logits, axis=-1)
    att = sd._ein(mode, "bhqk,bkhd->bqhd", probs, v).reshape(b, s, inner)
    x = x + sd._linear(p, att, w[f"{a}o.weight"])
    f = "layer.1.DenseReluDense."
    h = _rms(x, w["layer.1.layer_norm.weight"], eps)
    h = _gelu_new(sd._linear(p, h, w[f"{f}wi_0.weight"])) * sd._linear(
        p, h, w[f"{f}wi_1.weight"])
    return x + sd._linear(p, h, w[f"{f}wo.weight"])


def encode(p, w, c: dict, ids, mask=None):
    """The encoder's final states (B, S, d_model) for token ids (B, S); ``w``
    the file's tensors, ``c`` its sizes (HF ``config.json`` keys), ``mask``
    (B, S) of 0/1 or None."""
    eps = float(c.get("layer_norm_epsilon", 1e-6))
    heads, n = c["num_heads"], c["num_layers"]
    s = ids.shape[1]
    buckets = relative_buckets(s, s, c["relative_attention_num_buckets"],
                               c["relative_attention_max_distance"])
    masked = 0.0 if mask is None else jnp.where(
        jnp.asarray(mask)[:, None, None, :] > 0, 0.0, -jnp.inf)

    def bias_of(i):
        table = w[f"encoder.block.{i}.layer.0.SelfAttention."
                  "relative_attention_bias.weight"].astype(F32)
        return jnp.transpose(table[buckets], (2, 0, 1))[None] + masked

    shared = None if c.get("per_layer_bias") else bias_of(0)
    x = w["shared.weight"].astype(F32)[ids]  # not scaled by sqrt(d_model)
    block = sd._jitted(_block, p, heads, eps)
    for i in range(n):
        x = block(sd._sub(w, f"encoder.block.{i}."), x,
                  bias_of(i) if shared is None else shared)
    return _rms(x, w["encoder.final_layer_norm.weight"], eps)
