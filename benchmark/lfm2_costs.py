"""Operations and bytes the LFM2 mixture-of-experts decoder's algorithms need,
from their shapes: the family ``conv_moe_lm``'s FLOPs a round and the
numerators of ``shortconv_gate_roofline`` and ``flash_d64_roofline``. Every
count is a lower bound on the work (only the pairs the mask shows, only the
assignments held, every operand moved once, nothing recomputed), so a share
made from it cannot pass 100%. Kept with the benchmark, beside
``moe_costs.py``, whose ``attention_cost`` and ``visible_pairs`` it uses.
"""

from __future__ import annotations

from benchmark import moe_costs

MIXER_OF = {"conv": "conv", "full_attention": "gqa"}  # published layer_types -> the program's


def layers(config: dict) -> tuple:
    """``(mixer, routed)`` of each layer the configuration runs: the published
    0-based layers ``layers_run``, a layer's mixer from ``layer_types`` and
    its feed-forward dense where it is one of the ``num_dense_layers``
    leading ones."""
    run = config["layers_run"]
    if len(run) != config["num_hidden_layers"] or list(run) != sorted(set(run)):
        raise ValueError("layers_run must name num_hidden_layers published layers in order")
    return tuple((MIXER_OF[config["layer_types"][i]], i >= config["num_dense_layers"])
                 for i in run)


def mixers(config: dict) -> tuple:
    return tuple(mixer for mixer, _ in layers(config))


def dense_layers(config: dict) -> int:
    """Leading dense layers among those run (``layers_run`` ascends, so they
    lead)."""
    return sum(not routed for _, routed in layers(config))


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def conv_flops_per_token(config: dict) -> float:
    """The operator: 2 x multiply-accumulates of W_in (D x 3D) and W_out (D x
    D), and a channel's ``B * z``, K taps and ``C * c``."""
    d, taps = config["hidden_size"], config["conv_L_cache"]
    return 2.0 * (3 * d * d + d * d) + (2.0 + 2.0 * taps) * d


def attention_flops_per_token(config: dict, positions: int) -> float:
    """The four projections and ``QK^T`` and ``PV`` over the visible pairs
    (the norms of q and k and the rotation are not counted)."""
    d, width = config["hidden_size"], head_dim(config)
    q_width = config["num_attention_heads"] * width
    kv_width = config["num_key_value_heads"] * width
    return (2.0 * d * (2 * q_width + 2 * kv_width)
            + 4.0 * q_width * moe_costs.visible_pairs(positions, None) / positions)


def feed_forward_flops_per_token(config: dict, routed: bool) -> float:
    """The dense feed-forward, or the router and the experts held here for the
    expected ``k * held / outputs`` assignments; no shared expert."""
    d = config["hidden_size"]
    if not routed:
        return 6.0 * d * config["intermediate_size"]
    held_per_token = (config["num_experts_per_tok"] * config["num_experts"]
                      / config["moe_router_outputs"])
    return 2.0 * d * config["moe_router_outputs"] + (
        6.0 * d * config["moe_intermediate_size"] * held_per_token)


def forward_flops_per_token(config: dict, seq_len: int) -> float:
    """One token's training forward on this chip's share: each layer's mixer
    and feed-forward, and the tied head over the held vocabulary."""
    total = 2.0 * config["hidden_size"] * config["vocab_size"]
    for mixer, routed in layers(config):
        total += (conv_flops_per_token(config) if mixer == "conv"
                  else attention_flops_per_token(config, seq_len))
        total += feed_forward_flops_per_token(config, routed)
    return total


def gate_cost(tokens: int, channels: int, taps: int, backward: bool,
              bytes_per_element: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one call of the operator's elementwise chain at
    *stated* traffic. Forward: ``[tokens, 3 channels]`` read once, ``[tokens,
    channels]`` written; a multiply for ``B * z``, K multiply-adds, a multiply
    for ``C * c``. Backward: that input and the output's gradient read, the
    input's gradient written and the taps' ``[K, channels]`` in float32; the
    chain run again (``u`` and ``c`` are not among what is read), ``C``'s and
    ``c``'s gradients, the taps' transposed pass and their own gradient, and
    ``B``'s and ``z``'s. A byte moved buys one operation or less, so the
    chip's memory binds, not its vector unit."""
    elems = float(tokens * channels)
    if backward:
        return (5.0 + 6.0 * taps) * elems, bytes_per_element * 7.0 * elems + 4.0 * taps * channels
    return (2.0 + 2.0 * taps) * elems, bytes_per_element * 4.0 * elems


def attention_cost(note: dict, backward: bool) -> tuple[float, float]:
    """``moe_costs.attention_cost`` at the shapes of one ``attn/call`` note
    (``shape`` ``[B, H, T, d]``, ``q_heads_per_kv_head``, ``window``)."""
    batch, heads, t, d = note["shape"]
    return moe_costs.attention_cost(batch, heads, heads // note["q_heads_per_kv_head"], t, d,
                                    note["window"], backward)
