"""deeplearning4j_tpu — a TPU-native deep-learning framework.

A ground-up JAX/XLA/Pallas/pjit re-design providing the capabilities of the
DL4J stack (ND4J tensor API, SameDiff autodiff graphs, DataVec ETL, the DL4J
NN library, ParallelWrapper/SharedTrainingMaster distributed training, model
zoo, Keras/TF import) as an idiomatic TPU-first framework:

- one compiled SPMD program per training step (vs per-op JNI dispatch),
- functional pytree state (vs mutable INDArrays + workspaces),
- XLA collectives over ICI/DCN (vs Aeron UDP gradient sharing),
- Pallas kernels where the reference used cuDNN helpers.

Reference capability map: see SURVEY.md at the repo root. Reference classes
are cited in docstrings as ``ref: <path> — <Class>`` (structure per SURVEY.md;
the reference mount was empty during the survey, so citations are to the
upstream layout, not literal line numbers).
"""

import time as _time

_IMPORT_STARTED = _time.perf_counter()

from deeplearning4j_tpu.version import __version__  # noqa: E402

# Convenience top-level re-exports (lazy-ish: keep light to not force jax init
# ordering issues; submodules import jax themselves).
from deeplearning4j_tpu.nn.config import (  # noqa: E402
    NeuralNetConfiguration,
    SequentialConfig,
    GraphConfig,
)
from deeplearning4j_tpu.nn.model import SequentialModel, GraphModel  # noqa: E402
from deeplearning4j_tpu.observability import trace as _trace  # noqa: E402

__all__ = [
    "__version__",
    "NeuralNetConfiguration",
    "SequentialConfig",
    "GraphConfig",
    "SequentialModel",
    "GraphModel",
]

# what importing the package cost, as a span of the host timeline
_trace.record_span(
    "import.deeplearning4j_tpu", trace_id=_trace.new_id(),
    start=_trace.from_perf_counter(_IMPORT_STARTED), end=_trace.now())
