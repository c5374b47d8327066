"""Names of the device scopes of the train step.

Every call site wraps its layer's work in ``jax.named_scope(<name>)``
with a name from here.  JAX writes the scope path of each operation into
the HLO as ``metadata={op_name="jit(lm_train_step)/jvp(blocks)/..."}``,
and XLA keeps it on the optimized instructions, so the compiled text of
a step says which layer each instruction belongs to:

  ff            forward product of every N:M linear (core/operand)
  bp            its data gradient ``dx``
  wu            its weight gradient ``dw``
  attention     ``attn_apply``; its q/k/v/o linears stay ff/bp/wu
  moe_dispatch  router, top-k, slot assignment, dispatch and combine
                gathers, aux loss (not the expert FFN)
  blocks        the layer scan: norms, residuals, activations, stacking
  embed_head    embedding lookup, final norm, head logits and loss
  update        the weight update (optim/sgd): momentum, decay, SR-STE,
                the master update and the FF/BP operand generation,
                which XLA fuses together
"""

FF = "ff"
BP = "bp"
WU = "wu"
ATTENTION = "attention"
MOE_DISPATCH = "moe_dispatch"
BLOCKS = "blocks"
EMBED_HEAD = "embed_head"
UPDATE = "update"

LAYERS = (FF, BP, WU, ATTENTION, MOE_DISPATCH, BLOCKS, EMBED_HEAD, UPDATE)
