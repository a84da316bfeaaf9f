from chainermn_tpu.parallel.mesh import (
    DEFAULT_AXIS,
    INTER_AXIS,
    INTRA_AXIS,
    RankGeometry,
    make_3d_mesh,
    make_hierarchical_mesh,
    make_mesh,
)
from chainermn_tpu.parallel.fsdp import (
    fsdp_shard,
    fsdp_spec,
    jit_fsdp_train_step,
)
from chainermn_tpu.parallel.moe import (
    DroplessMoE,
    ExpertParallelMLP,
    GShardMoE,
    MoeStatsAccumulator,
)
from chainermn_tpu.parallel.gspmd import (
    gspmd_lm_train_step,
    megatron_opt_shard,
    megatron_param_specs,
    megatron_shard,
)
from chainermn_tpu.parallel.tensor import (
    ColumnParallelDense,
    RowParallelDense,
    TensorParallelAttention,
    TensorParallelMLP,
    reshard_tp_qkv,
)
from chainermn_tpu.parallel.sequence import (
    full_attention,
    ring_attention,
    sequence_parallel_attention,
    ulysses_attention,
)

__all__ = [
    "DEFAULT_AXIS",
    "INTER_AXIS",
    "INTRA_AXIS",
    "RankGeometry",
    "make_mesh",
    "make_hierarchical_mesh",
    "make_3d_mesh",
    "DroplessMoE",
    "ExpertParallelMLP",
    "GShardMoE",
    "MoeStatsAccumulator",
    "gspmd_lm_train_step",
    "megatron_param_specs",
    "megatron_shard",
    "megatron_opt_shard",
    "fsdp_shard",
    "fsdp_spec",
    "jit_fsdp_train_step",
    "ColumnParallelDense",
    "RowParallelDense",
    "TensorParallelAttention",
    "TensorParallelMLP",
    "reshard_tp_qkv",
    "full_attention",
    "ring_attention",
    "ulysses_attention",
    "sequence_parallel_attention",
]
