"""One rehearsal run of a cell (tiny widths, on the CPU, the harness's look
for a chip skipped), optionally with a fault planted under the timed path;
prints ``{"correct": ..., "check": ..., "metrics": ...}`` as its last line.

    python3 bench/tests/rehearse.py <workload> [--fault F] [--root DIR]
                                    [--trace] [--seed N]

``--root`` names another checkout (``BENCHMARK.json`` and ``bench/``) whose
cells, mixes and metric readers the harness finds by name.

Faults (``none`` plants nothing):

* ``stale_kv``    — the decode step returns its KV cache unchanged;
* ``half_batch``  — the upper half of the slot batch is left out of the
  decode step (its logits are never computed: all zero);
* ``token``       — one token of every slot in every decode chunk is altered
  where the chunk produces it;
* ``no_exchange`` — the MLP's row-parallel product is left as each chip's
  partial sum: the all-reduce between chips is left out (meshes only).
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def stale_kv():
    from repro.models import attention as A
    orig = A.apply_attention_decode_paged

    def f(params, x, cfg, policy, state, cache_pos, page_table):
        out, _ = orig(params, x, cfg, policy, state, cache_pos, page_table)
        return out, state
    A.apply_attention_decode_paged = f


def half_batch():
    from repro.models import lm
    orig = lm.forward_decode

    def f(params, tokens, cfg, policy, cache, **kw):
        logits, exits, new_cache = orig(params, tokens, cfg, policy, cache,
                                        **kw)
        half = logits.shape[0] // 2
        return logits.at[half:].set(0), exits, new_cache
    lm.forward_decode = f


def token():
    from repro.serve import engine as E
    orig = E.SlotEngine.decode

    def f(self, params, cache, st):
        cache, st, toks = orig(self, params, cache, st)
        vocab = self.run.arch.vocab_size
        return cache, st, toks.at[:, 0].set((toks[:, 0] + 1) % vocab)
    E.SlotEngine.decode = f


def no_exchange():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import xaif
    from repro.dist import sharding as shd
    from repro.models import lm

    def f(params, x, policy):
        g = xaif.call("gemm", policy, x, params["w_gate"], activation="silu")
        u = xaif.call("gemm", policy, x, params["w_up"])
        h = (g * u).astype(x.dtype)
        mesh = shd.current_ctx().mesh
        lead = (None,) * (h.ndim - 1)
        partial = jax.shard_map(
            lambda h, w: jnp.einsum("...f,fd->...d", h, w),
            mesh=mesh, in_specs=(P(*lead, "model"), P("model", None)),
            out_specs=P(*lead, None), check_vma=False)
        return partial(h, params["w_down"]).astype(x.dtype)
    lm.apply_mlp = f


FAULTS = {"none": lambda: None, "stale_kv": stale_kv,
          "half_batch": half_batch, "token": token,
          "no_exchange": no_exchange}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--fault", choices=sorted(FAULTS), default="none")
    ap.add_argument("--root", type=Path, default=ROOT)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--seed", type=int, default=3_000_000_017)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    FAULTS[args.fault]()
    from bench import harness
    out = harness.run(args.workload, args.seed, args.seconds, args.trace,
                      rehearse=True, require_chip=False, root=args.root,
                      log=lambda s: print(s, file=sys.stderr))
    print(json.dumps({"correct": out["correct"], "check": out["check"],
                      "metrics": out["metrics"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
