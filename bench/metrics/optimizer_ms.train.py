"""Device ms per traced step of the training step's optimizer: the ops
whose innermost layer scope is ``optim.adamw`` (the gradient norm, the
clip, the moments and the update; ``bench/scopes.py``)."""
from bench import scopes

UNIT = "ms"
#: The layer scopes this metric sums.
SCOPES = ("optim.adamw",)


def read(ctx):
    return scopes.layer_ms(ctx, *SCOPES)
