"""out_tok_s: output tokens that reached the host inside the window
(first tokens at their admission, the rest at their chunk's end), divided
by the window's seconds (host clock)."""
from bench import stats


def read(ctx):
    lo, hi = ctx["window"]
    firsts = [r.t_first_token for r in ctx["all_requests"]
              if r.t_first_token is not None]
    chunks = [(c[1], c[2]) for c in ctx["recorder"].chunks]
    return stats.delivered(firsts, chunks, lo, hi) / (hi - lo)
