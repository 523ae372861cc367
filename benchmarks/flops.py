"""Operations and bytes that the benchmark credits, counted from shapes.

2 operations per multiply-add. A train step is the forward and twice the forward
for the backward of every product. Causal attention is credited its causal half.
"""

#: matmuls of [T, D] x [D, T] size in each flash kernel: the forward forms S and
#: P V; the dq kernel replays S, forms dP and dS K; the dk/dv kernel replays S,
#: forms dP, P^T dO and dS^T Q
FLASH_MATMULS = {'fwd': 2, 'bwd_dq': 3, 'bwd_dkv': 4}


def transformer_train_flops(batch, seq_len, vocab, embed, layers, ffn):
    """Model operations of one train step of a decoder (forward and backward).

    Per token and layer, forward: qkv ``6 E^2``, attention output ``2 E^2``, MLP
    ``4 E F``, causal scores and values ``2 T E``; the unembedding ``2 E V`` once.
    Embedding lookups are gathers and count nothing."""
    per_token = layers * (8 * embed * embed + 4 * embed * ffn + 2 * seq_len * embed)
    per_token += 2 * embed * vocab
    return 3 * batch * seq_len * per_token


def flash_kernel_flops(kind, bh, seq_len, head_dim, causal=True):
    """Matmul operations of one call of a flash kernel over ``bh`` (batch x heads)
    sequences, the causal half only where ``causal``."""
    full = 2 * bh * seq_len * seq_len * head_dim
    return FLASH_MATMULS[kind] * (full // 2 if causal else full)


def flash_kernel_bytes(kind, bh, seq_len, head_dim, itemsize=2):
    """The least HBM traffic of one call: every operand read once, every result
    written once. Row statistics (lse, delta) are float32 columns."""
    tensor = bh * seq_len * head_dim * itemsize
    column = bh * seq_len * 4
    if kind == 'fwd':
        return 3 * tensor + tensor + column            # q k v in; o, lse out
    if kind == 'bwd_dq':
        return 4 * tensor + 2 * column + tensor        # q k v do, lse delta in; dq out
    if kind == 'bwd_dkv':
        return 4 * tensor + 2 * column + 2 * tensor    # ... in; dk dv out
    raise ValueError(kind)


def roofline_seconds(flops, nbytes, peak):
    """(least seconds, bound) on a chip of ``peak`` (a peaks.json entry)."""
    compute = flops / peak['bf16_flops_per_s']
    memory = nbytes / peak['hbm_bytes_per_s']
    return (compute, 'compute') if compute >= memory else (memory, 'memory')
