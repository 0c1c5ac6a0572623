"""Device ms per traced step of the training step's MoE dispatch, forward
and backward: the ops whose innermost layer scope is ``moe.route`` (the
router, the re-index, the aux and z losses) or ``moe.combine`` (the
scatter of the expert rows back to tokens; ``bench/scopes.py``)."""
from bench import scopes

UNIT = "ms"
#: The layer scopes this metric sums.
SCOPES = ("moe.route", "moe.combine")


def read(ctx):
    return scopes.layer_ms(ctx, *SCOPES)
