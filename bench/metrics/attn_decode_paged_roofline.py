"""attn_decode_paged_roofline (kernels): the Pallas paged decode-attention
calls inside the traced decode chunks — the least time they could take
over the live contexts (``flops.attn_decode_paged``: each live slot's K/V
read once), over their device time (device trace). Nothing to read where
no Pallas paged attention ran."""
from bench import devmetrics, flops


def read(ctx):
    evs = devmetrics.kernel_events(ctx, "attn_decode_paged")
    if not evs or ctx["peaks"] is None:
        return None
    pk, m = ctx["peaks"], ctx["model"]
    # one call per layer per step: the bound of a chunk's calls is its
    # steps' live contexts, once per layer and per device that ran them
    chunks = {}
    for e, _, rec in evs:
        chunks.setdefault(id(rec), rec)
    n_dev = len(ctx["trace"].devices)
    bound = 0.0
    for rec in chunks.values():
        for lens in devmetrics.live_steps(rec[3], ctx["engine"].chunk):
            if lens:
                c = flops.attn_decode_paged(lens, m["num_heads"],
                                            m["num_kv_heads"], m["head_dim"])
                bound += (m["num_layers"] * n_dev / ctx["chips"]
                          * flops.roofline_s(c, pk["bf16_flops"],
                                             pk["hbm_bytes_per_s"]))
    spent = sum((e.end - e.start) * 1e-9 for e, _, _ in evs)
    return 100.0 * bound / spent
