"""The busiest routed expert's assignments over the mean expert's, from
the engine's ``moe_expert_tokens`` counter (live tokens, summed over the
MoE layers and every prefill and decode step of the window): 1 is an even
load; with dropless experts the busiest sets a grouped product's length."""


def read(record, trace, ctx):
    counts = record.get("engine", {}).get("moe_expert_tokens")
    if not counts or sum(counts) == 0:
        return None
    return max(counts) * len(counts) / sum(counts)
