"""analytics_zoo_tpu — a TPU-native (JAX/XLA/Pallas/pjit) analytics + AI platform with the
capability surface of Analytics Zoo (see SURVEY.md for the reference blueprint)."""

from analytics_zoo_tpu.common.context import (
    ZooConf, ZooContext, get_context, init_context, init_nncontext, mesh)
from analytics_zoo_tpu.common import dtypes

__version__ = "0.1.0"

# the start-up timeline (PR 37): the package, and with it jax, is imported
from analytics_zoo_tpu.common.observability import get_startup as _startup
_startup().stamp("imported")
