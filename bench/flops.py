"""Operations and bytes from shapes: the yardstick for roofline shares and
model FLOP utilization. Nothing here reads the program; every count comes
from the configuration's ``model`` sizes and the call's shapes.

A dense GQA decoder layer (``model`` keys): q/k/v/o projections
(``num_heads``/``num_kv_heads`` heads of ``head_dim``), a gated MLP of
width ``d_ff`` (gate, up, down), two RMSNorms. The unembedding is a
``d_model`` x ``vocab_size`` matrix; the embedding is a table lookup (no
FLOPs, ``d_model`` values read per token).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple


def gemm(m: int, k: int, n: int, dtype_bytes: int = 2,
         bias: bool = False) -> Dict[str, float]:
    """One [m, k] x [k, n] product: 2mkn FLOPs; reads x and w (and the
    bias), writes the [m, n] result."""
    nbytes = dtype_bytes * (m * k + k * n + m * n + (n if bias else 0))
    return {"flops": 2.0 * m * k * n, "bytes": float(nbytes)}


def attn_decode_paged(ctx_lens: Iterable[int], num_heads: int,
                      num_kv_heads: int, head_dim: int,
                      dtype_bytes: int = 2) -> Dict[str, float]:
    """One decode-attention call over the live slots: for each slot with
    ``ctx`` cached positions, q.k and p.v cost 4 * heads * head_dim * ctx
    FLOPs, and its K and V rows (num_kv_heads * head_dim each) are read
    once; the query and the output are one row of heads each."""
    ctx = list(ctx_lens)
    tot = float(sum(ctx))
    flops = 4.0 * num_heads * head_dim * tot
    kv = 2.0 * num_kv_heads * head_dim * tot * dtype_bytes
    qo = 2.0 * len(ctx) * num_heads * head_dim * dtype_bytes
    return {"flops": flops, "bytes": kv + qo}


def layer_gemms(model: dict, rows: int) -> List[Tuple[int, int, int, bool]]:
    """(m, k, n, bias) of each projection of one layer at ``rows`` rows."""
    d, dh, ff = model["d_model"], model["head_dim"], model["d_ff"]
    hq, hkv = model["num_heads"], model["num_kv_heads"]
    b = bool(model.get("qkv_bias", False))
    return [(rows, d, hq * dh, b), (rows, d, hkv * dh, b),
            (rows, d, hkv * dh, b), (rows, hq * dh, d, False),
            (rows, d, ff, False), (rows, d, ff, False), (rows, ff, d, False)]


def step_gemms(model: dict, rows: int) -> List[Tuple[int, int, int, bool]]:
    """Every projection of one forward step at ``rows`` rows: each layer's,
    then the unembedding."""
    return (layer_gemms(model, rows) * model["num_layers"]
            + [(rows, model["d_model"], model["vocab_size"], False)])


def weight_params(model: dict) -> int:
    """Parameters a decode step must read: every projection and norm, and
    the unembedding. The embedding table is looked up, not read whole."""
    per_layer = sum(k * n + (n if b else 0)
                    for _, k, n, b in layer_gemms(model, 1))
    per_layer += 2 * model["d_model"]                       # two norms
    return (model["num_layers"] * per_layer + model["d_model"]
            + model["d_model"] * model["vocab_size"])


def kv_bytes_per_token(model: dict, dtype_bytes: int = 2) -> int:
    """K and V of one position over every layer."""
    return (2 * model["num_layers"] * model["num_kv_heads"]
            * model["head_dim"] * dtype_bytes)


def decode_step(model: dict, ctx_lens: Iterable[int],
                dtype_bytes: int = 2) -> Dict[str, float]:
    """One decode step over the live slots, each at its context length.

    FLOPs: 2 * (projection and unembedding parameters) per token, plus
    attention over each slot's context in every layer. Bytes: what the step
    must read at least — the weights once (norm scales are float32), each
    live slot's K/V, and one embedding row per token."""
    ctx = list(ctx_lens)
    n_tok = len(ctx)
    proj = sum(k * n for _, k, n, _ in step_gemms(model, 1))
    att = attn_decode_paged(ctx, model["num_heads"], model["num_kv_heads"],
                            model["head_dim"], dtype_bytes)
    flops = 2.0 * proj * n_tok + model["num_layers"] * att["flops"]
    norms = (2 * model["num_layers"] + 1) * model["d_model"]
    wbytes = (weight_params(model) - norms) * dtype_bytes + 4 * norms
    nbytes = (wbytes + kv_bytes_per_token(model, dtype_bytes) * sum(ctx)
              + n_tok * model["d_model"] * dtype_bytes)
    return {"flops": flops, "bytes": float(nbytes)}


def prefill(model: dict, length: int) -> float:
    """FLOPs of one causal prefill of ``length`` tokens (projections, and
    attention over the causal triangle), the unembedding at the last
    position only."""
    proj = sum(k * n for _, k, n, _ in layer_gemms(model, 1))
    att = (2.0 * model["num_heads"] * model["head_dim"]
           * length * (length + 1))                       # qk + pv, causal
    unembed = 2.0 * model["d_model"] * model["vocab_size"]
    return model["num_layers"] * (2.0 * proj * length + att) + unembed


def roofline_s(cost: Dict[str, float], peak_flops: float,
               peak_bw: float) -> float:
    """Least time the chip can take: the larger of the compute bound and
    the memory bound."""
    return max(cost["flops"] / peak_flops, cost["bytes"] / peak_bw)
