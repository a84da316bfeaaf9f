from chainermn_tpu.models.laguna import LagunaLM
from chainermn_tpu.models.mlp import MLP
from chainermn_tpu.models.qwen3_next import Qwen3NextLM
from chainermn_tpu.models.resnet import (
    AlexNet,
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from chainermn_tpu.models.smallthinker import SmallThinkerLM
from chainermn_tpu.models.transformer import (
    KVCacheKind,
    SlotStateKind,
    TransformerBlock,
    TransformerLM,
    generate,
    init_kv_caches,
    init_paged_kv_caches,
)
from chainermn_tpu.models.vision import GoogLeNet, InceptionBlock, VGG16

__all__ = [
    "MLP",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "AlexNet",
    "GoogLeNet",
    "InceptionBlock",
    "VGG16",
    "KVCacheKind",
    "LagunaLM",
    "Qwen3NextLM",
    "SlotStateKind",
    "SmallThinkerLM",
    "TransformerBlock",
    "TransformerLM",
    "generate",
    "init_kv_caches",
    "init_paged_kv_caches",
]
