# repro.core — Trust<T> delegation as a TPU-native distribution primitive.
#
# opspec.py    the typed spec layer: Field/OpSpec/TrustSchema, generated op
#              handles, submit-time validation (DESIGN.md §10)
# channel.py   the delegation channel (pack/transmit/serve/respond/unpack)
# trust.py     Trust / TrusteeGroup — the user-facing typed-handle +
#              apply()/apply_then() API
# engine.py    DelegationEngine / TrustSession — one multiplexed round for
#              all Trusts + the adaptive capacity planner (DESIGN.md §8)
# kvstore.py   DelegatedKVStore + make_kv_schema (paper §6.3)
# pagetable.py DelegatedPageTable — Trust-owned paged KV-cache page table
#              for continuous-batching decode (DESIGN.md §15)
# lockstore.py lock-analog baselines (Fig. 6 competitors)
# nested.py    launch()/nested delegation (chained channel rounds)
# routing.py   key -> trustee routers + workload generators
# meshctx.py   current-mesh + current-session threading for shard_map islands
# tracing.py   the runtime's host spans and device scopes (profiler trace)
from .opspec import Field, ListField, OpSpec, SchemaError, TrustSchema
from .channel import (ChannelConfig, ChannelInfo, DelegatedOp,
                      DelegationFuture, Grouping, Packed, Received,
                      check_response_structs, delegate, delegate_async,
                      delegate_drain, make_grouping, pack, respond,
                      serve_multiplex, serve_optable, transmit, unpack)
from .engine import (CapacityPlanner, DelegationEngine, TrustSession,
                     check_payload_fields)
from .trust import Trust, TrusteeGroup, TrustFuture, local_trustees
from .kvstore import (DelegatedKVStore, kv_reshard, make_kv_ops,
                      make_kv_schema)
from .pagetable import (DelegatedPageTable, SequentialPageTable,
                        initial_pagetable_state, make_pagetable_schema,
                        pagetable_reshard)
from .lockstore import (AtomicAddStore, FetchRMWStore, SequentialKVReference,
                        conflict_ranks)
from .meshctx import (constrain, current_mesh, current_session,
                      delegation_mode, set_delegation_mode, set_mesh,
                      set_session, survivors_mesh, use_mesh, use_session)
from .routing import partition_clients_trustees, trustee_device_slot
from .nested import launch_serve

__all__ = [
    "Field", "ListField", "OpSpec", "SchemaError", "TrustSchema",
    "DelegatedPageTable", "SequentialPageTable", "initial_pagetable_state",
    "make_pagetable_schema", "pagetable_reshard",
    "ChannelConfig", "ChannelInfo", "DelegatedOp", "DelegationFuture",
    "Grouping", "Packed", "Received", "check_response_structs",
    "delegate", "delegate_async", "delegate_drain", "make_grouping",
    "pack", "respond", "serve_multiplex", "serve_optable",
    "transmit", "unpack", "Trust", "TrusteeGroup", "TrustFuture",
    "local_trustees", "CapacityPlanner", "DelegationEngine", "TrustSession",
    "check_payload_fields", "DelegatedKVStore", "kv_reshard", "make_kv_ops",
    "make_kv_schema", "survivors_mesh", "AtomicAddStore",
    "FetchRMWStore", "SequentialKVReference", "conflict_ranks", "constrain",
    "current_mesh", "current_session", "delegation_mode",
    "set_delegation_mode", "set_session", "use_mesh", "use_session",
    "set_mesh", "partition_clients_trustees", "trustee_device_slot",
    "launch_serve",
]
