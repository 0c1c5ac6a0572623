"""Device ms per traced step of the training step's ops that carry no
layer scope a metric reads (the ``SCOPES`` of the other readers;
``bench/scopes.py``): patch embedding, merges, pooling, the head, the
loss, and residual adds that no scoped op absorbed. With the six scoped
metrics it splits the device's busy time."""
from bench import scopes

UNIT = "ms"


def read(ctx):
    return scopes.layer_ms(ctx, None)
