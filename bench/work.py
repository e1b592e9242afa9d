"""Operations a dense decoder requires, counted from its shapes (the
benchmark's yardstick for model FLOP/s utilisation).

Counted: every projection of every layer and the output head, at two
operations per multiply-add; causal attention as half the square (each
position attends to itself and the positions before it), whatever the
program computes. Not counted: recomputed operations, norms, softmax and
other elementwise work.
"""
from __future__ import annotations


def layer_matmul_params(dm: dict) -> int:
    d, H, K, hd, f = dm["d"], dm["H"], dm["K"], dm["hd"], dm["f"]
    return d * H * hd * 2 + d * K * hd * 2 + 3 * d * f


def attention_flops(dm: dict, context: int) -> int:
    """Scores and weighted sum of one query over ``context`` positions,
    all layers."""
    return dm["L"] * 4 * dm["H"] * dm["hd"] * context


def sequence_forward_flops(dm: dict, seq: int) -> int:
    """Forward pass of one sequence of ``seq`` tokens with the head at
    every position (training)."""
    proj = 2 * seq * (dm["L"] * layer_matmul_params(dm) + dm["d"] * dm["V"])
    attn = attention_flops(dm, 1) * seq * (seq + 1) // 2
    return proj + attn


def train_flops_per_token(dm: dict, seq: int) -> float:
    """Forward and backward (three forwards' worth) per token."""
    return 3.0 * sequence_forward_flops(dm, seq) / seq


def served_flops(dm: dict, prompt: int, first: int, last: int) -> int:
    """Tokens ``first`` .. ``last`` (from 1) of a request: the first
    needs the prompt's positions through every layer, each later one its
    fed-back predecessor; every token needs the head once."""
    fed_back = max(0, last - max(first, 2) + 1)
    fed = (prompt if first == 1 else 0) + fed_back
    proj = 2 * fed * dm["L"] * layer_matmul_params(dm)
    head = 2 * (last - first + 1) * dm["d"] * dm["V"]
    # the fed-back token at position j attends to j + 1 positions
    lo = prompt + max(first, 2) - 2
    context = fed_back * (2 * lo + fed_back + 1) // 2
    if first == 1:
        context += prompt * (prompt + 1) // 2
    return proj + head + attention_flops(dm, 1) * context
