"""The benchmark's own arithmetic: operations, bytes and least times.

Copied from the program's ``core/traffic.py`` (``scheme1_flops``,
``scheme2_flops``, the fused-kernel bytes) so that a change to the
program cannot change the yardstick, plus the model FLOPs of a served
token and the table of peaks keyed by ``device_kind``.

The work of an emulated site follows from the precision the cell
declares, not from the route the program takes: Scheme I at p slices
runs p(p+1)/2 int8 products, Scheme II with m moduli runs m.  A site's
least time is the larger of its int8 products at the int8 peak and its
bytes (operands in their stored dtype read once, the output written
once) at the HBM bandwidth.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import re

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Peaks:
    name: str
    int8_ops_per_s: float
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float


def peaks(device_kind: str, path=PEAKS_FILE) -> Peaks:
    """Peaks of the chip JAX reports as ``device_kind``; KeyError for a
    chip that is not in the table."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return Peaks(**table[device_kind])


def int8_products(spec: str) -> int:
    """int8 GEMMs one emulated product runs at the precision ``spec``
    states: 'ozaki1-p<p>' -> p(p+1)/2, 'ozaki2-m<m>' -> m.  Suffixes
    ('+cached', '@tpu', ...) do not change the count."""
    m = re.fullmatch(r"ozaki([12])-[pm](\d+)(?:[+@].*)?", spec)
    if m is None:
        raise ValueError(f"not an emulated precision spec: {spec!r}")
    n = int(m.group(2))
    return n * (n + 1) // 2 if m.group(1) == "1" else n


@dataclasses.dataclass(frozen=True)
class Site:
    """One emulated GEMM: ``batch`` products of (m, k) @ (k, n)."""
    name: str
    spec: str
    m: int
    k: int
    n: int
    batch: int = 1
    a_bytes: int = 4          # stored dtype of each operand and the output
    b_bytes: int = 4
    out_bytes: int = 4

    @property
    def products(self) -> int:
        return int8_products(self.spec)

    @property
    def int8_ops(self) -> int:
        """int8 operations (2 per multiply-add) over all products."""
        return self.batch * self.products * 2 * self.m * self.k * self.n

    @property
    def bytes(self) -> int:
        return self.batch * (self.a_bytes * self.m * self.k
                             + self.b_bytes * self.k * self.n
                             + self.out_bytes * self.m * self.n)

    def least_s(self, pk: Peaks) -> float:
        return max(self.int8_ops / pk.int8_ops_per_s,
                   self.bytes / pk.hbm_bytes_per_s)


def least_s(sites, pk: Peaks) -> float:
    """Least time of a call or a step: each site's, summed."""
    return sum(s.least_s(pk) for s in sites)


def gemm_flops(m: int, k: int, n: int) -> int:
    """Operations of the GEMM the emulation stands for (2MNK)."""
    return 2 * m * n * k


# ---------------------------------------------------------------------------
# A served decoder-only transformer (OLMo-style: dense attention, SwiGLU).
# ---------------------------------------------------------------------------

def pad_vocab(vocab: int, multiple: int = 512) -> int:
    return ((vocab + multiple - 1) // multiple) * multiple


def layer_params(cfg: dict) -> int:
    """Weights of one transformer layer: q, k, v, o and the SwiGLU FFN."""
    d, hd = cfg["d_model"], cfg["d_model"] // cfg["n_heads"]
    attn = d * cfg["n_heads"] * hd * 2 + d * cfg["n_kv_heads"] * hd * 2
    return attn + 3 * d * cfg["d_ff"]


def serve_step_sites(cfg: dict, lanes: int, chunk: int, view: int):
    """The emulated sites of one engine step of compiled shape
    (lanes, chunk) over per-lane cache views of ``view`` tokens, per
    layer times the layers, plus the logits of each lane's last row."""
    d, h, kvh = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, f, L = d // h, cfg["d_ff"], cfg["n_layers"]
    sites = cfg["gemm_sites"]
    act = 2 if cfg["dtype"] == "bfloat16" else 4
    rows = lanes * chunk

    def site(name, fam, m, k, n, batch=1):
        return Site(name, sites.get(fam, sites["default"]), m, k, n,
                    batch=batch * L, a_bytes=act, b_bytes=act,
                    out_bytes=act)

    out = [site("attn_q", "attn", rows, d, h * hd),
           site("attn_kv", "attn", rows, d, kvh * hd, batch=2),
           site("attn_o", "attn", rows, h * hd, d),
           site("ffn_in", "ffn", rows, d, f, batch=2),
           site("ffn_out", "ffn", rows, f, d),
           site("attn_qk", "attn_qk", chunk, hd, view, batch=lanes * h),
           site("attn_av", "attn_av", chunk, view, hd, batch=lanes * h)]
    logits = Site("logits", sites.get("logits", sites["default"]), lanes, d,
                  pad_vocab(cfg["vocab"]), a_bytes=act, b_bytes=act,
                  out_bytes=act)
    return out + [logits]


def served_token_flops(cfg: dict, position: int) -> int:
    """Model FLOPs of one useful token at ``position`` (0-based): the
    layer weights, and attention over the ``position + 1`` live keys."""
    d = cfg["d_model"]
    attn = 2 * 2 * d * (position + 1)          # q.k and weights.v
    return cfg["n_layers"] * (2 * layer_params(cfg) + attn)


def logits_flops(cfg: dict) -> int:
    """FLOPs of one row of logits over the published vocabulary."""
    return 2 * cfg["d_model"] * cfg["vocab"]


def step_model_flops(cfg: dict, start, n_new, emit) -> int:
    """Useful model FLOPs of one engine step: each lane's fresh tokens at
    their positions, and a row of logits for each lane that emits."""
    total = 0
    for s, n, e in zip(start, n_new, emit):
        s, n = int(s), int(n)
        if n:
            # sum over positions s .. s+n-1 of served_token_flops
            d, L = cfg["d_model"], cfg["n_layers"]
            total += n * L * 2 * layer_params(cfg)
            total += L * 4 * d * (n * (s + 1) + n * (n - 1) // 2)
        if e:
            total += logits_flops(cfg)
    return total
