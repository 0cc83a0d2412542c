"""Whole-step share of the chip's peak in training: the FLOPs the model
requires per token (forward and backward, no recompute, causal attention
over half the sequence on average) times the window's tokens per second,
over the peak bf16 FLOP/s in ``peaks.json``."""


def read(facts):
    if facts.get("kind") != "train" or not facts.get("peak"):
        return None
    return (100.0 * facts["flops_per_token"] * facts["tokens_per_s"]
            / facts["peak"]["flops_per_s"])
