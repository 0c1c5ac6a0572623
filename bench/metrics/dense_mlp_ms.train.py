"""Device ms per traced step of the training step's dense MLPs, forward
and backward: the ops whose innermost layer scope is ``swin.mlp`` (both
matmuls and the GELU; ``bench/scopes.py``)."""
from bench import scopes

UNIT = "ms"
#: The layer scopes this metric sums.
SCOPES = ("swin.mlp",)


def read(ctx):
    return scopes.layer_ms(ctx, *SCOPES)
