"""Model zoo — the standalone test/benchmark fixtures as real models
(ref: apex/transformer/testing/standalone_{gpt,bert}.py and the
1574-LoC transformer LM fixture; resnet mirrors examples/imagenet).

Importing the package loads every family (the surface lock and
packaging both want the full tree importable); reach for a submodule
directly if import cost matters.
"""

from apex_tpu.models import (  # noqa: F401
    bert, decoder, gpt, migrate, pretrain, resnet, t5)
from apex_tpu.models.migrate import (  # noqa: F401
    stack_scan_params,
    unstack_scan_params,
)

__all__ = ["bert", "decoder", "gpt", "migrate", "pretrain", "resnet", "t5",
           "stack_scan_params", "unstack_scan_params"]
