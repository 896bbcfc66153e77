"""Parallelism library — mesh, collectives, TP/PP/SP, fleet.

Reference analog: the whole distributed stack of SURVEY §2.2 — NCCL infra
(platform/nccl_helper.h), collective ops (operators/collective/), transpilers
(transpiler/collective.py), fleet API (incubate/fleet/), PipelineOptimizer
(optimizer.py:2677). Re-designed TPU-first: named mesh axes + GSPMD shardings
+ shard_map collectives replace NCCL rings and graph rewriting; ring
attention adds the sequence/context-parallel axis the reference lacked
(SURVEY §5 long-context note).
"""
from .collective import (  # noqa: F401
    all_gather,
    all_reduce,
    all_to_all,
    broadcast,
    reduce_scatter,
)
from .checkpoint import (  # noqa: F401
    Checkpointer, load_checkpoint, save_checkpoint)
from .env import get_rank, get_world_size, init_parallel_env  # noqa: F401
from .mesh import DistributedStrategy, auto_mesh, make_mesh  # noqa: F401
from .dgc import dgc_allreduce, sparse_allgather_exchange, top_k_sparsify  # noqa: F401
from .local_sgd import (  # noqa: F401
    average_params, local_sgd_step, replicate_params)
from .moe import (  # noqa: F401
    experts_ffn, init_moe_params, moe_ffn, moe_ffn_expert_parallel, route)
from .pipeline import GPipe, pipeline_step  # noqa: F401
from .ring_attention import ring_attention, ring_self_attention  # noqa: F401
from .tensor_parallel import (MEGATRON_RULES, annotate_tp,  # noqa: F401
                              annotate_tp_auto, derive_tp_specs)
