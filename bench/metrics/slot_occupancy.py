"""Share of decode lanes that carried a request: decoded tokens over
(decode iterations x slots), counted at the scheduler seam over the
window. Moves ``out_tok_s``."""


def read(ctx):
    w = ctx.window
    its = [n for t, n in w.log.iterations if w.t0 <= t <= w.t1 and n > 0]
    if not its:
        return None
    return 100.0 * sum(its) / (len(its) * ctx.cell.traffic["slots"])
