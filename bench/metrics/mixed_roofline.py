"""Step executables: the mixed program's share of its roofline, in %: the
longer of the time its needed FLOPs take at the chips' bf16 peak
(``bench.flops.step_flops``) and the time its needed bytes take at their
HBM bandwidth (``bench.step_bytes.step_bytes``: the weights once, the
valid KV read and the KV written), over its device time. Both are means
over the traced stretch: the traced mixed steps' work, the trace's
``mixed_step`` programs' seconds."""
from bench import flops, step_bytes


def read(run):
    t = run.trace
    if t is None:
        return None
    s, n = t.module_s("mixed_step")
    recs = [r for r in run.trace_steps if r.kind == "mixed"]
    if not n or not recs or s <= 0:
        return None
    c, p = run.config, run.peaks
    need_s = max(
        sum(flops.step_flops(c, r) for r in recs) / (run.chips * p.bf16_flops),
        sum(step_bytes.step_bytes(c, r) for r in recs)
        / (run.chips * p.hbm_bytes_per_s))
    return 100.0 * (need_s / len(recs)) / (s / n)
