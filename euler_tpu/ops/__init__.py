from euler_tpu.ops import mp_ops  # noqa: F401
from euler_tpu.ops.mp_ops import (  # noqa: F401
    gather,
    grid_add,
    scatter,
    scatter_add,
    scatter_max,
    scatter_mean,
    scatter_softmax,
)
