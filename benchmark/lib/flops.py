"""Operations the algorithm needs, from shapes alone.

Convention: one multiply-add is 2 operations; the backward pass of a
matmul costs twice its forward; recomputation never counts. Attention
is counted causal (half of the seq x seq square)."""


def lm_forward_flops_per_token(m: dict, context: float) -> float:
    """Forward operations for one token that attends to `context`
    earlier positions (itself included): the four attention
    projections, scores and weighted values over the context, the two
    feed-forward matmuls, per layer; plus the head."""
    e, f, v = m["hidden_size"], m["ffn_dim"], m["vocab_size"]
    per_layer = 2 * (4 * e * e) + 2 * (2 * context * e) + 2 * (2 * e * f)
    return m["num_hidden_layers"] * per_layer + 2 * e * v


def lm_train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward + backward (3x forward) for one token of a causal
    sequence of `seq_len`: the mean context is (seq_len + 1) / 2."""
    return 3.0 * lm_forward_flops_per_token(m, (seq_len + 1) / 2.0)

