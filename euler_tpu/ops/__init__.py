import os

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
from euler_tpu.ops.pallas_kernels import gather_weighted_sum  # noqa: F401

# 'off' → pure XLA segment ops; 'auto' → fused Pallas kernel on TPU;
# 'interpret' → Pallas interpreter (testing)
_PALLAS_MODE = os.environ.get("EULER_TPU_PALLAS", "off")


def set_pallas(mode: str) -> None:
    global _PALLAS_MODE
    assert mode in ("off", "auto", "interpret", "pallas")
    _PALLAS_MODE = mode


def pallas_mode() -> str:
    return _PALLAS_MODE
