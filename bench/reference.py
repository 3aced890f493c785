"""Plain reference of a served model: the forward pass of a decoder-only
transformer under a per-layer ternary / int8 precision policy, written from
the configuration file alone, in straightforward `jax.numpy`.

It imports nothing of the program under test and takes nothing the program
made. Its weights are drawn here from the run's seed by the served model's
recipe: normal draws in the parameter dtype scaled by 1/sqrt(fan-in) (the
embedding by 0.02), from a key tree split(PRNGKey(seed), 8) -> embed 0,
block 0 at 1, the last block at 2, lm_head 3, middle block i at
split(split(key 4, layers - 2)[i - 1], 1)[0]; in a block, split 4 ->
attention 0 (split 4: qkv 0, out 1) and FFN 1 (dense: split 2, up and
down; experts: split 4, router, up, down, shared up/down from split 2 of
the last). They are quantized here:

  ternary weights  q = sign(w) where |w| > t·mean|w| (t = ternary_threshold,
                   mean over the whole tensor), per-output-channel scale
                   sum|w·q| / sum|q|;
  int8 weights     codes round(w / s), s = max|w| / 127 per output channel;
  ternary acts     per row: q = sign(x) where |x| > t·mean|x|, scale mean|x|;
  int8 acts        codes round(x / int8_act_scale), clipped to ±127.

A quantized linear is the exact integer dot of the codes times the two
scales. The residual stream, projection outputs and attention output are
rounded to the stream dtype the configuration states; norms, rotary phases,
softmax and attention sums are float32 at the highest matmul precision.

`run` takes whole sequences teacher-forced, one layer at a time over a
list of sequences, so that only one layer's weights are live.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes and numerics of one configuration file."""
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    eps: float
    rope_theta: float
    param_dtype: str
    stream_dtype: str
    body: tuple            # (weight precision, activation precision)
    first_last: tuple
    lm_head: tuple
    ternary_threshold: float
    int8_act_scale: float
    experts: int = 0
    shared_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0
    capacity_factor: float = 0.0

    @classmethod
    def from_config(cls, conf: dict) -> "Model":
        served, q = conf["served"], conf["quant"]
        return cls(
            d=served["hidden_size"], layers=served["num_hidden_layers"],
            heads=served["num_attention_heads"],
            kv_heads=served["num_key_value_heads"],
            head_dim=served["head_dim"], d_ff=served["intermediate_size"],
            vocab=served["vocab_size"], eps=served["rms_norm_eps"],
            rope_theta=served["rope_theta"],
            param_dtype=q["param_dtype"], stream_dtype=q["stream_dtype"],
            body=tuple(q["body"]), first_last=tuple(q["first_last"]),
            lm_head=tuple(q["lm_head"]),
            ternary_threshold=q["ternary_threshold"],
            int8_act_scale=q["int8_act_scale"],
            experts=served.get("n_routed_experts", 0),
            shared_experts=served.get("n_shared_experts", 0),
            top_k=served.get("num_experts_per_tok", 0),
            expert_d_ff=served.get("moe_intermediate_size", 0),
            capacity_factor=served.get("capacity_factor", 0.0))

    def layer_precision(self, i: int) -> tuple:
        return self.first_last if i in (0, self.layers - 1) else self.body


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _normal(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) * (1.0 / fan_in ** 0.5)


def top_keys(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed), 8)


def block_key(keys, m: Model, i: int):
    """Block i's key (see the module doc)."""
    if i == 0:
        return keys[1]
    if i == m.layers - 1:
        return keys[2]
    mids = jax.random.split(keys[4], m.layers - 2)
    return jax.random.split(mids[i - 1], 1)[0]


def _quant_weight(w, prec: str, thr: float):
    """(codes as float32 in the layout (..., in, out), per-out scale)."""
    w = w.astype(jnp.float32)
    if prec == "ternary":
        t = thr * jnp.mean(jnp.abs(w)) + 1e-8
        q = jnp.where(w > t, 1.0, jnp.where(w < -t, -1.0, 0.0))
        scale = jnp.sum(jnp.abs(w) * jnp.abs(q), axis=-2) / (
            jnp.sum(jnp.abs(q), axis=-2) + 1e-6)
        return q, scale
    top = {"int8": 127.0, "int4": 7.0}[prec]
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / top + 1e-12
    codes = jnp.clip(jnp.round(w / s), -top, top)
    return codes, jnp.squeeze(s, -2)


def _linear_weights(key, shape, fan_in, prec, m: Model):
    w = _normal(key, shape, fan_in, jnp.dtype(m.param_dtype))
    if prec[0] == "none":
        return {"w": w.astype(jnp.float32)}
    q, s = _quant_weight(w, prec[0], m.ternary_threshold)
    return {"q": q, "s": s}


def block_weights(key, m: Model, prec: tuple) -> dict:
    """One block's quantized weights, drawn from its key."""
    d, h, hk, dh = m.d, m.heads, m.kv_heads, m.head_dim
    ks = jax.random.split(key, 4)
    a = jax.random.split(ks[0], 4)
    out = {"qkv": _linear_weights(a[0], (d, (h + 2 * hk) * dh), d, prec, m),
           "out": _linear_weights(a[1], (h * dh, d), h * dh, prec, m)}
    if m.experts:
        f, e = m.expert_d_ff, m.experts
        r = jax.random.split(ks[1], 4)
        out["router"] = {"w": _normal(r[0], (d, e), d,
                                      jnp.dtype(m.param_dtype))}
        out["up"] = _linear_weights(r[1], (e, d, 2 * f), d, prec, m)
        out["down"] = _linear_weights(r[2], (e, f, d), f, prec, m)
        fs = f * m.shared_experts
        k1, k2 = jax.random.split(r[3])
        out["shared_up"] = _linear_weights(k1, (d, 2 * fs), d, prec, m)
        out["shared_down"] = _linear_weights(k2, (fs, d), fs, prec, m)
    else:
        k1, k2 = jax.random.split(ks[1])
        out["up"] = _linear_weights(k1, (d, 2 * m.d_ff), d, prec, m)
        out["down"] = _linear_weights(k2, (m.d_ff, d), m.d_ff, prec, m)
    return out


def outer_weights(keys, m: Model) -> dict:
    pdt = jnp.dtype(m.param_dtype)
    embed = jax.random.normal(keys[0], (m.vocab, m.d), pdt) * 0.02
    return {"embed": embed,
            "lm_head": _linear_weights(keys[3], (m.d, m.vocab), m.d,
                                       m.lm_head, m)}


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _round(x, m: Model):
    return x.astype(jnp.dtype(m.stream_dtype))


def qlinear(w: dict, x, prec: tuple, m: Model):
    """x (..., K) -> (..., N) float32, before rounding to the stream."""
    xf = x.astype(jnp.float32)
    if prec[0] == "none":
        return jnp.matmul(xf, w["w"], precision=HIGHEST)
    aprec = prec[1]
    if aprec == "ternary":
        a = jnp.mean(jnp.abs(xf), axis=-1, keepdims=True)
        t = m.ternary_threshold * a + 1e-8
        xq = jnp.where(xf > t, 1.0, jnp.where(xf < -t, -1.0, 0.0))
    elif aprec == "int8":
        a = jnp.float32(m.int8_act_scale)
        xq = jnp.clip(jnp.round(xf / a), -127.0, 127.0)
    else:
        raise ValueError(f"activation precision {aprec!r}")
    acc = jax.lax.dot_general(
        xq.astype(jnp.int8), w["q"].astype(jnp.int8),
        (((xq.ndim - 1,), (w["q"].ndim - 2,)), ((), ())),
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * w["s"] * a


def rmsnorm(x, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


def rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (T, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, m: Model):
    """Causal GQA over one sequence. q (T,H,dh), k/v (T,Hk,dh), float32."""
    t = q.shape[0]
    g = m.heads // m.kv_heads
    qg = q.reshape(t, m.kv_heads, g, m.head_dim)
    s = jnp.einsum("thgd,shd->hgts", qg, k, precision=HIGHEST)
    s = s / m.head_dim ** 0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgts,shd->thgd", p, v, precision=HIGHEST)
    return o.reshape(t, m.heads * m.head_dim)


def _ffn(w, x, prec, m: Model, up="up", down="down"):
    h = _round(qlinear(w[up], x, prec, m), m).astype(jnp.float32)
    g, u = jnp.split(h, 2, axis=-1)
    act = _round(jax.nn.silu(g) * u, m)
    return qlinear(w[down], act, prec, m)


def _moe(w, x, prec, m: Model, n_prompt, capacity):
    """Top-k routed experts (softmax gates, renormalized over the chosen
    k) plus the always-on shared experts. The prompt's first `n_prompt`
    positions were served in one call whose experts each hold `capacity`
    assignments, taken in position-major, rank-minor order; assignments
    past it are dropped. Later positions were decoded one token per call,
    where no expert can be over capacity."""
    logits = _round(jnp.matmul(x.astype(jnp.float32),
                               w["router"]["w"].astype(jnp.float32),
                               precision=HIGHEST), m).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, m.top_k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    t = x.shape[0]
    prompt = (jnp.arange(t) < n_prompt)[:, None, None]
    sel = jax.nn.one_hot(topi, m.experts, dtype=jnp.int32) * prompt
    flat = sel.reshape(t * m.top_k, m.experts)
    slot = jnp.sum((jnp.cumsum(flat, axis=0) - flat) * flat, axis=-1)
    keep = jnp.where(prompt[:, :, 0], slot.reshape(t, m.top_k) < capacity,
                     True)
    gate = jnp.where(keep, topv, 0.0)
    y = jnp.zeros(x.shape[:-1] + (m.d,), jnp.float32)
    for e in range(m.experts):
        we = {"up": jax.tree.map(lambda a: a[e], w["up"]),
              "down": jax.tree.map(lambda a: a[e], w["down"])}
        ye = _round(_ffn(we, x, prec, m), m).astype(jnp.float32)
        y = y + ye * jnp.sum(jnp.where(topi == e, gate, 0.0), axis=-1,
                             keepdims=True)
    y = _round(y, m).astype(jnp.float32)
    sh = _round(_ffn(w, x, prec, m, "shared_up", "shared_down"), m)
    return y + sh.astype(jnp.float32)


def moe_capacity(m: Model, n_prompt: int, page_size: int,
                 cache_len: int) -> int:
    """Assignments each expert holds in the call that served a prompt of
    n_prompt tokens: the prompt right-padded to the next page_size * 2**i
    (or cache_len), times top_k / experts, times the capacity factor,
    rounded up to a multiple of 4, at least 4."""
    b = page_size
    while b < min(n_prompt, cache_len):
        b *= 2
    b = min(b, cache_len)
    c = int(b * m.top_k / m.experts * m.capacity_factor)
    return max(4, (c + 3) // 4 * 4)


def block(w, x, m: Model, prec: tuple, n_prompt=0, capacity=0):
    """One pre-norm block over one sequence x (T, D) in the stream dtype.
    Returns (x, k, v): the block's output and the keys (after the rotary
    phase) and values it would cache, (T, Hk, dh) each."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _round(rmsnorm(x, m.eps), m)
    y = _round(qlinear(w["qkv"], h, prec, m), m)
    hq, hk, dh = m.heads, m.kv_heads, m.head_dim
    q = y[:, :hq * dh].reshape(t, hq, dh)
    k = y[:, hq * dh:(hq + hk) * dh].reshape(t, hk, dh)
    v = y[:, (hq + hk) * dh:].reshape(t, hk, dh)
    q = _round(rope(q, pos, m.rope_theta), m).astype(jnp.float32)
    k = _round(rope(k, pos, m.rope_theta), m).astype(jnp.float32)
    o = _round(attention(q, k, v.astype(jnp.float32), m), m)
    a = _round(qlinear(w["out"], o, prec, m), m)
    x = _round(x.astype(jnp.float32) + a.astype(jnp.float32), m)
    h2 = _round(rmsnorm(x, m.eps), m)
    f = (_moe(w, h2, prec, m, n_prompt, capacity) if m.experts
         else _ffn(w, h2, prec, m))
    x = _round(x.astype(jnp.float32) + _round(f, m).astype(jnp.float32), m)
    return x, _round(k, m), _round(v, m)


def head(w, x, m: Model):
    h = _round(rmsnorm(x, m.eps), m)
    return qlinear(w["lm_head"], h, m.lm_head, m)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _block_weights_jit(key, m, prec):
    return block_weights(key, m, prec)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block_jit(w, x, m, prec, n_prompt, capacity):
    return block(w, x, m, prec, n_prompt, capacity)


@functools.partial(jax.jit, static_argnums=(2,))
def _score(w, x, m, at, cand):
    """At positions `at`: each row's best logit, its argmax, and for the
    candidate tokens `cand` (n, C) their logits and ranks (how many
    logits of the row lie above theirs; 0 = the row's argmax)."""
    lg = head(w, x[at], m)                              # (n, V)
    got = jnp.take_along_axis(lg, cand, axis=-1)
    rank = jnp.sum(lg[:, None, :] > got[:, :, None], axis=-1)
    return jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1), got, rank


def run(m: Model, seed: int, seqs: list, length: int, *, kv_layers: int = 0,
        depth: int | None = None, positions: list | None = None,
        cands: list | None = None, prompts: list | None = None,
        page_size: int = 0):
    """Teacher-forced pass over token sequences.

    seqs[i] is padded to `length` (attention is causal, so padding after a
    position never reaches it) and one program serves every sequence.
    Returns (kv, scores): kv[i][l] = (k, v) host arrays of sequence i's
    first len(seqs[i]) positions at layer l < kv_layers; scores[i] =
    (best (n,), argmax (n,), cand_logits (n, C), cand_ranks (n, C)) at
    positions[i] for the candidate token ids cands[i] (n, C). `depth` stops after that many
    layers (keys and values only, no scores). Expert layers need each
    sequence's prompt length (`prompts`) and the deployment's page size,
    which set the capacity of the call that served the prompt."""
    keys = top_keys(seed)
    outer = jax.jit(outer_weights, static_argnums=1)(keys, m)
    xs = []
    for s in seqs:
        toks = np.zeros(length, np.int32)
        toks[:len(s)] = s
        xs.append(_round(jnp.take(outer["embed"], jnp.asarray(toks), axis=0),
                         m))
    del outer["embed"]
    kv = [[] for _ in seqs]
    caps = [(0, 0)] * len(seqs)
    if m.experts:
        caps = [(n, moe_capacity(m, n, page_size, length)) for n in prompts]
    for i in range(m.layers if depth is None else depth):
        prec = m.layer_precision(i)
        w = _block_weights_jit(block_key(keys, m, i), m, prec)
        for j, s in enumerate(seqs):
            xs[j], k, v = _block_jit(w, xs[j], m, prec, jnp.int32(caps[j][0]),
                                     jnp.int32(caps[j][1]))
            if i < kv_layers:
                kv[j].append((np.asarray(k[:len(s)]), np.asarray(v[:len(s)])))
        del w
    scores = []
    if positions is not None and depth is None:
        for x, at, c in zip(xs, positions, cands):
            r = _score(outer, x, m, jnp.asarray(at, jnp.int32),
                       jnp.asarray(c, jnp.int32))
            scores.append(tuple(np.asarray(v) for v in r))
    return kv, scores
