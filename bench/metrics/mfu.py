"""mfu: the whole train step's share of the chips' bf16 peak.

Model FLOPs per token are 6*N + 12*n_layers*(n_heads*head_dim)*seq (PaLM,
appendix B): N counts the weight-matrix parameters a token multiplies,
output head included and the embedding lookup left out; recomputed
(rematerialised) work does not count.  Tokens per second come from the
untraced window of the same run (host clock).
"""


def matrix_params(arch: dict) -> int:
    d, f, v = arch["d_model"], arch["d_ff"], arch["vocab_size"]
    q = arch["n_heads"] * arch["head_dim"]
    kv = arch["n_kv_heads"] * arch["head_dim"]
    mlp = (3 if arch["mlp"] == "swiglu" else 2) * d * f
    return arch["n_layers"] * (2 * d * q + 2 * d * kv + mlp) + v * d


def flops_per_token(arch: dict, seq: int) -> float:
    attn = 12 * arch["n_layers"] * arch["n_heads"] * arch["head_dim"] * seq
    return 6.0 * matrix_params(arch) + attn


def read(run):
    if not run.tokens_per_s or not run.peaks:
        return None
    return 100.0 * flops_per_token(run.arch, run.seq) * run.tokens_per_s \
        / (run.chips * run.peaks["bf16_flops"])
