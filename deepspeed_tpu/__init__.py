"""deepspeed_tpu — a TPU-native large-model training & inference framework.

Capability surface of DeepSpeed v0.7.5 (reference ``deepspeed/__init__.py``)
re-designed for JAX/XLA: ``initialize()`` builds a training engine whose
forward/backward/step are jitted SPMD programs over a named device mesh;
``init_inference()`` builds a kernel-fused inference engine. ZeRO, tensor,
pipeline, expert, and sequence parallelism are PartitionSpecs over mesh axes
(see ``deepspeed_tpu/parallel/mesh.py``), not process groups.
"""

from deepspeed_tpu.version import __version__  # noqa: F401

from deepspeed_tpu import comm  # noqa: F401
from deepspeed_tpu.runtime.config import DeepSpeedConfig  # noqa: F401
from deepspeed_tpu.runtime.sentinel import DivergenceError  # noqa: F401
from deepspeed_tpu.parallel.mesh import (  # noqa: F401
    MeshTopology,
    get_default_topology,
    set_default_topology,
)


def initialize(*args, **kwargs):
    """Build a DeepSpeedEngine (reference deepspeed/__init__.py:51).

    Imported lazily so light-weight users (config/comm only) avoid pulling the
    full runtime.
    """
    try:
        from deepspeed_tpu.runtime.engine import initialize as _initialize
    except ModuleNotFoundError as e:  # pragma: no cover
        raise NotImplementedError(
            "deepspeed_tpu.runtime.engine is not available in this build"
        ) from e

    return _initialize(*args, **kwargs)


def init_inference(*args, **kwargs):
    """Build an InferenceEngine (reference deepspeed/__init__.py:225)."""
    try:
        from deepspeed_tpu.inference.engine import init_inference as _init_inference
    except ModuleNotFoundError as e:  # pragma: no cover
        raise NotImplementedError(
            "deepspeed_tpu.inference.engine is not available in this build"
        ) from e

    return _init_inference(*args, **kwargs)


def add_config_arguments(parser):
    """Attach --deepspeed/--deepspeed_config argparse flags
    (reference deepspeed/__init__.py:209)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed-TPU configurations")
    group.add_argument(
        "--deepspeed",
        default=False,
        action="store_true",
        help="Enable DeepSpeed-TPU (helper flag for argument parsing)",
    )
    group.add_argument(
        "--deepspeed_config", default=None, type=str,
        help="Path to the DeepSpeed-TPU JSON config file",
    )
    return parser
