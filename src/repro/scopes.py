"""Stable names of the train step's layers on the device.

Each layer runs under ``jax.named_scope(<name>)``.  A scope is metadata
only: it costs nothing when the step runs.  XLA keeps it in every
instruction's ``op_name`` (``jit(train_step)/.../jvp(attention)/dot``; the
backward reads ``transpose(jvp(attention))``, or sits under a transposed
enclosing loop), so a profiler trace, whose events are named by HLO
instruction, can be read back by layer.  Names nest: the innermost one on
an instruction's path is its layer.  The benchmark's per-layer metrics read
these exact strings, so rename a scope only together with them.
"""
from __future__ import annotations

EMBED = "embed"                    # token embedding lookup
LAYERS = "layers"                  # the layer scan's own slices and stacks
ATTENTION = "attention"            # norm + attention + residual sub-block
MOE = "moe"                        # norm + MoE layer + residual sub-block
FFN = "ffn"                        # the dense FFN sub-block in its place
ROUTER = "moe.router"              # gating (top-k) and its losses
COUNTS = "moe.counts"              # local expert counts and their all-gather
LP_SOLVE = "moe.lp_solve"          # the in-graph LP solve of replica loads
ALG1 = "moe.alg1"                  # rounding, Alg. 1 routing, load summaries
DISPATCH = "moe.dispatch"          # plan, row layout and the all-to-all out
EXPERT_FFN = "moe.expert_ffn"      # grouped expert FFN (+ tensor-axis psum)
GROUPED_FFN_BWD = "grouped_ffn_bwd"  # the grouped FFN's custom backward
COMBINE = "moe.combine"            # all-to-all back and the gated sum
LM_HEAD_LOSS = "lm_head_loss"      # final norm, LM head, cross entropy
GRAD_ACCUM = "grad_accum"          # the micro-batch loop and its sums
OPTIMIZER = "optimizer"            # master<->working copy, clip, AdamW

TRAIN_STEP = (EMBED, LAYERS, ATTENTION, MOE, ROUTER, COUNTS, LP_SOLVE, ALG1,
              DISPATCH, EXPERT_FFN, GROUPED_FFN_BWD, COMBINE, LM_HEAD_LOSS,
              GRAD_ACCUM, OPTIMIZER)
ALL = TRAIN_STEP + (FFN,)
