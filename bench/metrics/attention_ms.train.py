"""Device ms per traced step of the training step's window attention,
forward and backward: the ops whose innermost layer scope is
``swin.attention`` (the shift rolls, window partition and reverse, qkv,
scores, relative bias, softmax, proj; ``bench/scopes.py``)."""
from bench import scopes

UNIT = "ms"
#: The layer scopes this metric sums.
SCOPES = ("swin.attention",)


def read(ctx):
    return scopes.layer_ms(ctx, *SCOPES)
