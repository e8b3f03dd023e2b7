"""Model FLOP utilisation of the whole serving step, in percent: the
operations of every real prompt token and output token processed in
the traced window (two per weight multiplied, plus attention at the
real context; ``counts``), over window x chips x peak bf16 rate."""
import counts


def read(run):
    if run.trace is None:
        return None
    m = run.cfg
    flops = sum(counts.prefill_flops(m, c.positions)
                for c in run.calls("prefill"))
    flops += sum(counts.decode_step(m, c.contexts)[0]
                 for c in run.calls("decode"))
    peak = run.peaks()["bf16_flops"]
    return 100.0 * flops / (run.trace.window_s * run.chips * peak)
