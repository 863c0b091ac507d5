"""Rehearsal: a reducer that arrives as a new file."""

from lib.reducers import reducer


@reducer
def expert_flops_share_pct(ctx, args):
    """Share of the required forward FLOPs of a token that its experts'
    matmuls are, from the architecture's own count."""
    parts = ctx["arch"].forward_flops_per_token(ctx["model"], ctx["seq_len"])
    if "experts" not in parts:
        return None
    return (100.0 * ctx["model"]["num_hidden_layers"] * parts["experts"]
            / parts["total"])
