"""Names of the model step's parts, as ``jax.named_scope`` puts them in
the compiled program's op metadata (``op_name=".../<scope>/..."``).

A profiler trace attributes each device operation to the scope its op
name carries; the benchmark imports these constants, so renaming one
here renames it there.  Scopes change metadata only: the compiled
program and its outputs are the same with or without them.
"""
EMBED = "embed"            # token (and position) embedding
ATTN_PROJ = "attn_proj"    # the q/k/v projections with rope, and wo
ATTN_KV = "attn_kv"        # KV write, page gather, scores and values
MLP = "mlp"                # norm2 and the feed-forward block
LM_HEAD = "lm_head"        # final norm, unembedding and argmax
ALL = (EMBED, ATTN_PROJ, ATTN_KV, MLP, LM_HEAD)
