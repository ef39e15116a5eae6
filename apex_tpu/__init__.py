"""apex_tpu — a TPU-native training-acceleration framework.

A brand-new JAX/XLA/Pallas framework with the capabilities of NVIDIA Apex
(reference: caaatch22/apex): mixed-precision opt levels O0-O5, fused
optimizers built on a Pallas fused-update engine (the TPU equivalent of
apex's multi_tensor_apply CUDA machinery), fused layers (layernorm/rmsnorm,
scaled masked softmax, RoPE, dense+gelu, xentropy, flash attention), a
data-parallel runtime (DDP-equivalent psum-mean, SyncBatchNorm, LARC), and a
Megatron-style tensor/sequence/pipeline-parallel transformer library — all
expressed over a single `jax.sharding.Mesh` with XLA collectives instead of
NCCL process groups.

Top-level layout mirrors the reference's public surface
(reference `apex/__init__.py`):

    apex_tpu.amp             — mixed precision engine      (ref: apex/amp)
    apex_tpu.optimizers      — fused optimizers            (ref: apex/optimizers)
    apex_tpu.normalization   — FusedLayerNorm/FusedRMSNorm (ref: apex/normalization)
    apex_tpu.parallel        — DDP / SyncBN / LARC         (ref: apex/parallel)
    apex_tpu.transformer     — TP/SP/PP library            (ref: apex/transformer)
    apex_tpu.contrib         — production specials         (ref: apex/contrib)
    apex_tpu.multi_tensor    — fused update engine         (ref: apex/multi_tensor_apply + csrc/)
"""

import logging as _logging

__version__ = "0.1.0"


def _setup_logger() -> None:
    # Rank-aware library logger; the reference injects a (PID, ranks)
    # formatter at import (ref: apex/__init__.py:26-39). On TPU the
    # process index is `jax.process_index()`, resolved lazily so importing
    # apex_tpu never forces backend initialization.
    logger = _logging.getLogger("apex_tpu")
    if logger.handlers:
        return
    handler = _logging.StreamHandler()
    handler.setFormatter(
        _logging.Formatter("%(levelname)s [apex_tpu pid=%(process)d] %(message)s")
    )
    logger.addHandler(handler)
    logger.setLevel(_logging.WARNING)


_setup_logger()

from apex_tpu import multi_tensor  # noqa: E402,F401
from apex_tpu import amp  # noqa: E402,F401
from apex_tpu import optimizers  # noqa: E402,F401
from apex_tpu import normalization  # noqa: E402,F401
from apex_tpu import parallel  # noqa: E402,F401
from apex_tpu import transformer  # noqa: E402,F401
from apex_tpu import contrib  # noqa: E402,F401
from apex_tpu import moe  # noqa: E402,F401
from apex_tpu import rnn  # noqa: E402,F401
from apex_tpu import fp16_utils  # noqa: E402,F401
from apex_tpu import runtime  # noqa: E402,F401
from apex_tpu import telemetry  # noqa: E402,F401  — before resilience (it publishes here)
from apex_tpu import mesh  # noqa: E402,F401  — GSPMD substrate (needs telemetry)
from apex_tpu import resilience  # noqa: E402,F401  — needs runtime first
from apex_tpu import serving  # noqa: E402,F401  — needs telemetry + resilience
from apex_tpu import profiler  # noqa: E402,F401
from apex_tpu import testing  # noqa: E402,F401
