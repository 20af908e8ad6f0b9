"""Operations and bytes the algorithms NEED, from shapes alone.

Recomputed work does not count (a flash backward recomputes the scores; the
algorithm does not need it twice). Every function returns plain numbers; the
peaks they are held against are in `peaks.json`, keyed by `device_kind`.
"""


def bert_train_step_flops(cfg, B, S, M):
    """Forward + backward of one BERT MLM+NSP step, the hand count of
    `bench.py:_bench_bert` copied (the original is listed for deletion in
    PERF.md): 6 FLOPs per dense parameter per token (2 forward, 4 backward),
    12*L*H*S per token for the attention score and context matmuls, the MLM
    head on the M masked positions only, the pooler and NSP head once a row.
    """
    H, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    I = cfg["intermediate_size"]
    n_dense = L * (4 * H * H + 2 * H * I)
    flops_token = 6 * n_dense + 12 * L * H * S
    head = 6 * (H * H + H * V) * M + 6 * (H * H + 2 * H)
    return flops_token * B * S + head * B


def flash_attention_cost(B, heads, S, head_dim, itemsize):
    """One layer's attention core at [B, heads, S, head_dim], forward and
    backward: (flops, bytes) each. Forward: QK^T and PV, 2*S*S*d each a
    head. Backward needs dV, dP, dQ, dK: four such matmuls; the score
    recompute of the flash form is not needed work. Bytes: forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes dq, dk,
    dv (the log-sum-exp rows are S floats a head, counted too)."""
    mat = 2 * B * heads * S * S * head_dim
    arr = B * heads * S * head_dim * itemsize
    lse = B * heads * S * 4
    return {"fwd": (2 * mat, 4 * arr + lse),
            "bwd": (4 * mat, 8 * arr + 2 * lse)}


def roofline_seconds(flops, nbytes, peak):
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def decoder_request_flops(cfg, prompt_len, new_tokens):
    """Forward FLOPs the decoder needs to serve one request: every prompt
    and generated position but the last through the layers (2 per dense
    parameter, 4*h per attended position per layer), and the unembedding
    for the `new_tokens` sampled positions only."""
    h, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    m = cfg.get("mlp_ratio", 4) * h
    n = prompt_len + new_tokens - 1          # positions run through
    dense = L * (4 * h * h + 2 * h * m)
    attended = n * (n + 1) // 2              # sum of context lengths
    return 2 * dense * n + 4 * h * L * attended + 2 * h * V * new_tokens
