"""Device ms per traced step of the training step's LayerNorms, forward
and backward: the ops whose innermost layer scope is ``swin.norm``
(``bench/scopes.py``)."""
from bench import scopes

UNIT = "ms"
#: The layer scopes this metric sums.
SCOPES = ("swin.norm",)


def read(ctx):
    return scopes.layer_ms(ctx, *SCOPES)
