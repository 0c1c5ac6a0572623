"""Device ms per traced step of the training step's expert FFN, forward
and backward: the ops whose innermost layer scope is ``moe.experts`` (the
expert kernels and the row gather ahead of them; ``bench/scopes.py``).
Unlike ``moe_kernel_roofline.train`` it finds the kernels by scope, not
by their names."""
from bench import scopes

UNIT = "ms"
#: The layer scopes this metric sums.
SCOPES = ("moe.experts",)


def read(ctx):
    return scopes.layer_ms(ctx, *SCOPES)
