"""mfu: the whole train step's share of the chips' bf16 peak.

Model FLOPs per token come from the configuration's reference
(``flops_per_token``; for the dense decoder, ``refmodel``'s 6*N +
12*n_layers*(n_heads*head_dim)*seq, PaLM appendix B); recomputed
(rematerialised) work does not count.  Tokens per second come from the
untraced window of the same run (host clock).
"""


def read(run):
    if not run.tokens_per_s or not run.peaks:
        return None
    return 100.0 * run.flops_per_token * run.tokens_per_s \
        / (run.chips * run.peaks["bf16_flops"])
