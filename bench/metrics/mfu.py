"""Model operations done in the window over what the chip's int8 peak
could do in it: prompts whose first token came in the window (every
position through the blocks, the lm_head once) and each decoded token
(every weight once, attention over its context), counted at the
scheduler seam with ``bench/work.py``. Moves ``out_tok_s``."""


def read(ctx):
    w, cfg = ctx.window, ctx.cell.config
    ops = 0
    for rid, (_, t_first) in w.log.first.items():
        if w.t0 <= t_first <= w.t1:
            ops += ctx.work.prompt_ops(cfg, w.log.prompt_len[rid])
    for rid, times in w.log.token_times(w.t0, w.t1).items():
        s = w.log.prompt_len[rid]
        all_t = [t for t, _ in w.log.tokens[rid]]
        for j, t in enumerate(all_t):
            if j and w.t0 <= t <= w.t1:
                ops += ctx.work.decode_ops(cfg, s + j - 1)
    if not ops:
        return None
    return 100.0 * ops / (w.seconds * ctx.peaks["int8_ops_per_s"])
