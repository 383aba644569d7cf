"""distributedfft_tpu — TPU-native distributed 3D FFT framework.

A from-scratch JAX/XLA re-design of the capabilities of the reference
CUDA/MPI library eggersn/DistributedFFT: slab and pencil domain
decompositions of 3D R2C/C2R (and C2C) FFTs, executed as single jitted XLA
programs of local FFTs and mesh collectives over ICI/DCN, with the
reference's plan/execute API shape, testcase semantics, benchmark timer and
evaluation tooling.
"""

from .params import (
    AUTO,
    CommMethod,
    Config,
    FFTNorm,
    GlobalSize,
    PartitionDims,
    PencilPartition,
    SendMethod,
    SlabPartition,
    SlabSequence,
    block_sizes,
    block_starts,
    padded_extent,
    parse_comm_method,
)
from .parallel.mesh import (
    PENCIL_AXES,
    SLAB_AXIS,
    best_pencil_grid,
    make_pencil_mesh,
    make_slab_mesh,
)
from .parallel.multihost import (
    global_from_local,
    maybe_initialize,
    process_local_slices,
)
from .models.base import DistFFTPlan
from .models.batched2d import Batched2DFFTPlan
from .models.pencil import PencilFFTPlan
from .models.slab import SlabFFTPlan
from .resilience import GuardViolation
from .solvers import (
    NavierStokes2D,
    NavierStokes3D,
    PoissonSolver,
    SpectralConvolver,
    make_convolver,
    make_solver,
)

__all__ = [
    "AUTO", "CommMethod", "Config", "FFTNorm", "GlobalSize", "PartitionDims",
    "PencilPartition", "SendMethod", "SlabPartition", "SlabSequence",
    "block_sizes", "block_starts", "padded_extent", "parse_comm_method",
    "PENCIL_AXES", "SLAB_AXIS", "best_pencil_grid", "make_pencil_mesh",
    "make_slab_mesh", "Batched2DFFTPlan", "DistFFTPlan", "GuardViolation",
    "NavierStokes2D", "NavierStokes3D", "PencilFFTPlan", "PoissonSolver",
    "SlabFFTPlan", "SpectralConvolver", "global_from_local",
    "make_convolver", "make_solver", "maybe_initialize",
    "process_local_slices",
]

__version__ = "0.1.0"
