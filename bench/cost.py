"""Operations and bytes that the served model's work needs, from shapes.

Counted from the configuration and from the calls the benchmark observed
(decode ticks with their slots' positions, prefills with their prompt
lengths), never from the program's compiled HLO, so the yardstick reads the
same work whatever implements it: padding rows, padded capacity slots and
recomputation count as no work.

A quantized linear of M rows, K inputs and N outputs needs 2·M·K·N
operations, reading its packed weights once (K·N·bits/8 bytes plus a
float32 scale per output), its bf16 input rows and writing its bf16 output
rows. Its operations run at the int8 peak when both operands are 8 bits
or narrower, else at the bf16 peak.
"""
from __future__ import annotations

import dataclasses
import json
import os

BITS = {"binary": 1, "ternary": 2, "int4": 4, "int8": 8, "none": 16}
ACT_BYTES = 2          # bf16 activations and KV entries


def peaks(kind: str, path: str | None = None) -> dict:
    """The peak table's row for a device kind; an unknown kind is an error."""
    path = path or os.path.join(os.path.dirname(__file__), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    try:
        return table["devices"][kind]
    except KeyError:
        raise KeyError(f"device kind {kind!r} is not in {path} (have "
                       f"{sorted(table['devices'])}); add its published "
                       "peaks there") from None


@dataclasses.dataclass(frozen=True)
class Linear:
    name: str
    k: int
    n: int
    wprec: str
    aprec: str

    def narrow(self) -> bool:
        return max(BITS[self.wprec], BITS[self.aprec]) <= 8

    def work(self, m: int) -> tuple[float, float]:
        """(operations, bytes) of one call on m rows."""
        ops = 2.0 * m * self.k * self.n
        w = self.k * self.n * BITS[self.wprec] / 8 + 4 * self.n
        return ops, w + ACT_BYTES * m * (self.k + self.n)


def least_time(ops: float, nbytes: float, narrow: bool, pk: dict) -> float:
    """Seconds the chip needs at best: the larger of compute and memory."""
    rate = pk["int8_ops"] if narrow else pk["bf16_flops"]
    return max(ops / rate, nbytes / pk["hbm_bytes_per_s"])


def expert_work(tokens: int, top_k: int, touched: int, d: int, f: int,
                wprec: str) -> tuple[float, float]:
    """(operations, bytes) of one routed-expert layer (gated up d -> 2f,
    down f -> d) over `tokens` tokens: the routed assignments
    (tokens x top_k) count, padded capacity rows do not. Bytes: the
    weights of the `touched` experts that received an assignment, read
    once, and each assignment's bf16 rows in and out of both
    projections."""
    a = tokens * top_k
    ops = 2.0 * a * (d * 2 * f + f * d)
    w = touched * ((d * 2 * f + f * d) * BITS[wprec] / 8 + 4 * (2 * f + d))
    rows = ACT_BYTES * a * ((d + 2 * f) + (f + d))
    return ops, w + rows


@dataclasses.dataclass(frozen=True)
class Experts:
    """A routed-expert layer: top_k of `experts` gated FFNs of width f."""
    experts: int
    top_k: int
    d: int
    f: int
    wprec: str
    aprec: str

    def narrow(self) -> bool:
        return max(BITS[self.wprec], BITS[self.aprec]) <= 8

    def touched(self, tokens: int) -> float:
        """Experts that `tokens` tokens reach, expected under uniform
        routing (random weights route near uniformly)."""
        return self.experts * (1 - (1 - self.top_k / self.experts) ** tokens)

    def work(self, tokens: int) -> tuple[float, float]:
        return expert_work(tokens, self.top_k, self.touched(tokens), self.d,
                           self.f, self.wprec)


@dataclasses.dataclass(frozen=True)
class ModelCost:
    """The served model's linears and attention geometry, from a
    configuration file (bench/configs/*.json). Each layer is (linears,
    experts or None); `router` is the bf16 router linear of expert layers,
    which runs outside the quantized GEMM kernels."""
    layers: tuple
    lm_head: Linear
    heads: int
    kv_heads: int
    head_dim: int
    router: Linear | None = None

    @classmethod
    def from_config(cls, conf: dict) -> "ModelCost":
        s, q = conf["served"], conf["quant"]
        d, h, hk, dh = (s["hidden_size"], s["num_attention_heads"],
                        s["num_key_value_heads"], s["head_dim"])
        f, n = s["intermediate_size"], s["num_hidden_layers"]
        e = s.get("n_routed_experts", 0)
        layers = []
        for i in range(n):
            wp, ap = q["first_last"] if i in (0, n - 1) else q["body"]
            lins = [Linear("qkv", d, (h + 2 * hk) * dh, wp, ap),
                    Linear("out", h * dh, d, wp, ap)]
            ex = None
            if e:
                fs = f * s["n_shared_experts"]
                lins += [Linear("shared_up", d, 2 * fs, wp, ap),
                         Linear("shared_down", fs, d, wp, ap)]
                ex = Experts(e, s["num_experts_per_tok"], d, f, wp, ap)
            else:
                lins += [Linear("up", d, 2 * f, wp, ap),
                         Linear("down", f, d, wp, ap)]
            layers.append((tuple(lins), ex))
        router = Linear("router", d, e, "none", "none") if e else None
        return cls(tuple(layers), Linear("lm_head", d, s["vocab_size"],
                                         *q["lm_head"]), h, hk, dh, router)

    def linears(self):
        for lins, _ in self.layers:
            yield from lins

    def experts(self):
        return [ex for _, ex in self.layers if ex is not None]

    # -- the quantized GEMMs -----------------------------------------------

    def qgemm_least_s(self, rows: int, head_rows: int, pk: dict) -> float:
        """Least seconds of every qgemm call of one forward over `rows`
        tokens whose logits are taken at `head_rows` of them."""
        t = sum(least_time(*lin.work(rows), lin.narrow(), pk)
                for lin in self.linears())
        t += sum(least_time(*ex.work(rows), ex.narrow(), pk)
                 for ex in self.experts())
        return t + least_time(*self.lm_head.work(head_rows),
                              self.lm_head.narrow(), pk)

    # -- attention -----------------------------------------------------------

    def kv_bytes_per_token(self) -> int:
        """Bytes of keys and values one layer caches per token."""
        return 2 * self.kv_heads * self.head_dim * ACT_BYTES

    def decode_kv_bytes(self, positions) -> float:
        """KV bytes one layer's decode attention must read for slots at
        these positions (each reads its own entry and every earlier one)."""
        return float(sum(p + 1 for p in positions)) * self.kv_bytes_per_token()

    def attn_ops(self, ctx: float) -> float:
        """QK and PV operations of attention over `ctx` (query, key) pairs,
        over all layers."""
        return 4.0 * ctx * self.heads * self.head_dim * len(self.layers)

    # -- whole model -----------------------------------------------------------

    def model_least_s(self, tokens: int, head_rows: int, attn_ctx: float,
                      pk: dict) -> float:
        """Seconds at peak for the model operations of `tokens` tokens
        (2 x weights touched per token, logits at `head_rows`) plus
        attention over `attn_ctx` (query, key) pairs: each layer's
        operations at the peak of its operand width, attention at bf16."""
        rate = lambda narrow: pk["int8_ops"] if narrow else pk["bf16_flops"]
        s = sum(lin.work(tokens)[0] / rate(lin.narrow())
                for lin in self.linears())
        s += sum(ex.work(tokens)[0] / rate(ex.narrow())
                 for ex in self.experts())
        if self.router is not None:
            s += len(self.experts()) * self.router.work(tokens)[0] / rate(
                False)
        s += self.lm_head.work(head_rows)[0] / rate(self.lm_head.narrow())
        return s + self.attn_ops(attn_ctx) / pk["bf16_flops"]
