"""Device ms a traced step under the language model's head and loss,
forward and backward: the projection onto the vocabulary that the
model itself makes (tied: `<Model>/Transpose` and `<Model>/Mult`;
untied: the layer `head`), the reshape in front of the loss and
`SoftMaxCrossEntropy` (its fused kernels read
`…/SoftMaxCrossEntropy/softmax_xent_fwd`: a named kernel adds its name
to the scope)."""
from perfbench.harness import scope_trace

LAYER = "model math"
UNIT = "ms"
MOVES = "train_items_per_s"
# an operator straight under the model (no layer below it), or the
# untied head's layer
SCOPES = (r"^[^./]+/(?:Mult|Transpose|Reshape|SoftMaxCrossEntropy)(?:/|$)"
          r"|^[^./]+\.head/")


def read(run):
    return scope_trace.step_ms(run, SCOPES)
