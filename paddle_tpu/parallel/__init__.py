"""paddle_tpu.parallel — the distributed stack.

Reference analog: python/paddle/distributed/ (L5 in SURVEY.md). Exposed
both as paddle_tpu.parallel and paddle_tpu.distributed.
"""
from .env import (  # noqa: F401
    init_parallel_env, get_rank, get_world_size, is_initialized,
    ParallelEnv, device_count, local_device_count)
from .mesh import (  # noqa: F401
    build_mesh, set_global_mesh, get_mesh, use_mesh, sharding_for,
    shard_value, constraint, remap_spec_axes, remap_specs, tp_specs, P)
from .topology import (  # noqa: F401
    CommunicateTopology, HybridCommunicateGroup, CommGroup,
    set_hybrid_communicate_group, get_hybrid_communicate_group)
from .collective import (  # noqa: F401
    ReduceOp, all_reduce, all_gather, broadcast, barrier, scatter, reduce,
    reduce_scatter, all_to_all, send, recv, new_group, get_group, wait,
    psum, pmean, pmax, ppermute, axis_index)
from .data_parallel import DataParallel  # noqa: F401
from .checkpoint import (  # noqa: F401
    save_sharded, load_sharded, save_train_state, load_train_state,
    verify_checkpoint, CheckpointManager, CheckpointCorruptError,
    AsyncSaveError, HostSnapshot, Converter)
# NOTE: .resilience is NOT imported here — it imports
# distributed.launch.heartbeat, and distributed/__init__ imports this
# package; import it directly (paddle_tpu.parallel.resilience).
from .sharding import (  # noqa: F401
    group_sharded_parallel, save_group_sharded_model, GroupShardedStage2,
    GroupShardedStage3, GroupShardedOptimizerStage2, shard_model_stage3,
    shard_optimizer_state)
from .compression import (  # noqa: F401
    compressed_psum, dgc_compress, dgc_decompress, dgc_psum,
    local_sgd_sync)
from .host_pipeline import HostPipeline  # noqa: F401
from .pipeline import (  # noqa: F401
    spmd_pipeline, pipeline_forward, PipelineLayer, LayerDesc,
    SharedLayerDesc)
from .mp_layers import (  # noqa: F401
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    ParallelCrossEntropy)
from .random import (  # noqa: F401
    RNGStatesTracker, get_rng_state_tracker, model_parallel_random_seed)
from .moe import (  # noqa: F401
    MoELayer, moe_ffn, topk_gating, compute_capacity, GATES)
from . import fleet  # noqa: F401


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """reference: paddle.distributed.spawn. Single-controller JAX drives all
    local chips from one process — spawn degenerates to a direct call. A
    request for nprocs>1 would otherwise "pass" while silently running
    world_size=1, so it warns loudly."""
    if nprocs not in (-1, 0, 1):
        import warnings
        warnings.warn(
            f"paddle_tpu.distributed.spawn(nprocs={nprocs}) runs func ONCE "
            f"in-process: JAX is single-controller (all local chips belong "
            f"to this process; parallelism comes from the mesh, not from "
            f"worker processes). For true multi-process jobs use "
            f"`python -m paddle_tpu.distributed.launch --nproc_per_node "
            f"{nprocs}`.", RuntimeWarning, stacklevel=2)
    func(*args)


def launch():
    from .launch.main import main
    main()
