"""Stage scopes: the names the program puts on the work inside a jitted
train step (ISSUE 25).

``stage("lookup")`` is ``jax.named_scope("det.lookup")``: metadata on the
operations traced under it, carried into the compiled program's ``op_name``
paths and from there into a profiler capture's per-operation metadata
(``jit(det_train_step)/det.model/jvp(det.lookup)/det.lookup/jit(_take)/gather``;
the backward pass under ``transpose(``:
``.../det.model/transpose(jvp(det.acts))/det.acts/mul``). It costs nothing
when the step runs and needs no clock matching: whoever reads a trace takes
the last ``det.<stage>`` of an operation's path, the innermost scope, and
has its stage. ``staged("lookup")`` is the decorator form, for a
function that is one stage from top to bottom.

One flat vocabulary, in step order. Names are lower-case, hold no blank and
are not the name of a JAX primitive (a trace reader that classes operations
by primitive matches ``/gather:``, ``/sort:`` at a path's end).

  ids        input preparation and the dp->mp id exchange
  lookup     row gather, decode, combine, the tap add
  acts       mp->dp activation exchange and output assembly; its transpose
             under autodiff is the gradient exchange
  model      the value_and_grad of the model's loss (the embedding's scopes
             nest inside it and win)
  dense_opt  the dense optimizer's update and its application
  contrib    tap gradients + residuals -> per-row contributions
  dedup      the canonical id sort and the duplicate sum (sort, permutation
             gather, prefix, segment-sum, representatives)
  apply      the row update itself: scatter-adds, state re-reads, the delta

A model's own blocks nest inside ``model`` and win there, as the embedding's
do. They are declared here too, in `MODEL_STAGES`, apart from the engine's
eight: a step over a model without such a block holds none of them.

  attn       a transformer block's attention: projections, rotary, scores
  latent     inside ``attn``, and winning there: what multi-head latent
             attention puts in front of the scores (the two down-projections
             with their norms, the two up-projections, the rotary on the
             narrow parts, the assembly of q, k and v)
  shortconv  a gated short convolution: in-projection, gates, the taps over
             packed documents, out-projection
  mlp        a dense SwiGLU block
  router     an expert layer's scores over all experts and the top-k choice
  experts    the held experts' part: pair sort, gathers, grouped products,
             the weighted combine
  shared     a shared expert: the SwiGLU every token passes beside its routed
             experts
  head       final norm, logits over the vocabulary's slice, the loss
"""

import functools

import jax

__all__ = ["MODEL_STAGES", "PREFIX", "STAGES", "STEP_NAME", "stage", "staged"]

PREFIX = "det."
STAGES = ("ids", "lookup", "acts", "model", "dense_opt", "contrib", "dedup",
          "apply")
MODEL_STAGES = ("attn", "latent", "shortconv", "mlp", "router", "experts",
                "shared", "head")
# the jitted train steps' function name: traces say jit(det_train_step) and
# the compiled module is jit_det_train_step
STEP_NAME = "det_train_step"


def stage(name: str):
    """``jax.named_scope("det." + name)`` for a name of `STAGES` or
    `MODEL_STAGES`."""
    if name not in STAGES + MODEL_STAGES:
        raise ValueError(f"unknown stage {name!r}; the stages are "
                         f"{STAGES + MODEL_STAGES}")
    return jax.named_scope(PREFIX + name)


def staged(name: str):
    """Decorator: the whole function is traced under ``stage(name)``. A new
    scope per call, because a scope object keeps what it restores on exit on
    itself and one shared by every call would not survive re-entry."""
    stage(name)                      # an unknown name fails at import

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with stage(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap
