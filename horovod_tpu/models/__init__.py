"""Model zoo: the parity workloads from the reference's examples
(ResNet family, MNIST models) plus the multi-axis transformer flagship."""

from horovod_tpu.models.mnist import MnistCNN, MnistMLP  # noqa: F401
from horovod_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from horovod_tpu.models.transformer import (  # noqa: F401
    GPT2_BLOCK,
    BlockSpec,
    Transformer,
    TransformerConfig,
    get_param_specs,
    looped_loss,
    record_loop_stats,
)
