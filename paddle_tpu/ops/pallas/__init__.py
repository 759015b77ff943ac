"""Pallas TPU kernels.  One routing rule for all of them lives here."""
import jax

from ...distributed.mesh import auto_axes
from ...flags import get_flag


def kernel_enabled(flag: str, partitions_itself: bool = False) -> bool:
    """Whether the kernel behind ``FLAGS_<flag>`` is the route here:
    always in interpret mode (CPU tests; the interpreter lowers to
    plain HLO), else on a TPU backend where no multi-device mesh axis
    is left to GSPMD — Mosaic kernels cannot be partitioned
    automatically, so under such a mesh the XLA composition runs
    unless the kernel's wrapper ``partitions_itself`` with
    ``shard_map``."""
    if not get_flag(flag):
        return False
    if get_flag("pallas_interpret"):
        return True
    return jax.default_backend() == "tpu" \
        and (partitions_itself or not auto_axes())


from . import flash_attention  # noqa: E402
from . import ragged_paged_attention  # noqa: E402
