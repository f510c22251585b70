"""Model FLOP/s utilization of CRDNN training: the forward and backward
operations the utterances of the window need at their real lengths
(``flops.crdnn_train_flops``), over the traced window, the chips and the
chip's bf16 peak."""


def read(run):
    flops = run.window.totals["flops"]
    return 100.0 * flops / (run.trace.window_s * run.chips
                            * run.peaks["flops_bf16"])
