"""tpu_mx.parallel — mesh/SPMD layer (the reference's KVStore+launcher tier
re-designed for ICI/DCN collectives; SURVEY §2.3, §5.7, §5.8)."""
from .fleet import Fleet, MembershipChange, reshard_live
from .mesh import Mesh, NamedSharding, P, hybrid_mesh, local_mesh, make_mesh
from .moe import (DroplessMoE, MoEFFN, dropless_route, load_census,
                  moe_sharding_rules)
from .pipeline import pipeline_apply, stack_stage_params
from .ring_attention import attention, local_flash_attention, ring_attention
from .ulysses import get_sp_strategy, set_sp_strategy, ulysses_attention
from .train_step import (CompiledTrainStep, apply_rules, fsdp_rules,
                         sharding_for)
