"""siddhi_tpu — a TPU-native streaming SQL / complex event processing framework.

Brand-new implementation of the capability surface of Siddhi 5.x
(https://github.com/siddhi-io/siddhi; mounted read-only at /root/reference)
re-architected for JAX/XLA: queries compile to pure functions over columnar
event micro-batches `(state, batch) -> (state', outputs)`, partition keys
shard across the TPU mesh, group-by aggregates run as segmented scans, and
pattern NFAs advance as vectorized transitions.  See SURVEY.md.
"""
import os

# XLA:CPU's fusion emitters (jaxlib 0.9.0) miscompile some of our jitted
# pattern steps (LLVM IR verifier failure in fusion_compiler.cc — e.g. a
# 2-column (long,int) partitioned NFA step) and compile slower than the
# legacy emitters.  Opt out before the backend initializes.  The flag is a
# CPU-backend option that every XLA build of this installation parses
# (libtpu included — checked on the v5e, PERF.md "PR 21"), so it is safe to
# carry in XLA_FLAGS whichever backend ends up in use.
if "--xla_cpu_use_fusion_emitters" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_cpu_use_fusion_emitters=false")

import jax                                                       # noqa: E402

# LONG attributes and epoch-ms timestamps need 64-bit ints (i32 overflows in
# 2038 and on any epoch-ms value); XLA:TPU emulates s64.  DOUBLE still maps
# to f32 on device (core/event.py) since TPUs have no f64.
jax.config.update("jax_enable_x64", True)

from .core.event import Event                                    # noqa: E402
from .core.runtime import (                                      # noqa: E402
    InputHandler,
    QueryCallback,
    SiddhiAppRuntime,
    SiddhiManager,
    StreamCallback,
)
from . import query_api                                          # noqa: E402

__version__ = "0.1.0"
__all__ = [
    "Event", "InputHandler", "QueryCallback", "SiddhiAppRuntime",
    "SiddhiManager", "StreamCallback", "query_api",
]
