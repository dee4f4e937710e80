"""paddle_tpu.serving — the inference serving plane.

Continuous-batching engine over a fixed-shape KV cache: requests share
one preallocated decode batch, prefill is shape-bucketed AND batched
(same-bucket admissions ride one dispatch, as many as the bucket's one
program has rows: fewer the longer the bucket), and the decode step
compiles exactly once per engine geometry. KV memory is block-paged:
a fixed pool of KV blocks with per-request block tables, a ref-counted
allocator, and a rolling-hash prefix cache so a shared system prompt
prefills once and is referenced by later requests (copy-on-write at
the boundary block) — each request pays blocks for its actual need
instead of a full ``max_len`` row. With
``FLAGS_serving_spec_tokens`` = K > 0 the engine runs draft–verify
speculative decoding: an n-gram self-drafter proposes K tokens per
slot and one fixed-shape verify forward commits up to K+1 tokens per
step, token-identical to the plain greedy path.

Scaling is two orthogonal axes: ``FLAGS_serving_mesh`` runs one engine
tensor-parallel on a ``("data", "model")`` mesh (params and the paged
KV pool head-sharded via NamedSharding, every step under pjit), and
``FLAGS_serving_replicas`` puts a :class:`ReplicaRouter` in front of N
data-parallel engine replicas (least-loaded routing by queue depth +
free KV blocks, shed/drain semantics, :class:`AutoscalePolicy`-driven
replica scaling). With ``FLAGS_serving_slo_ttft_ms`` set the engine
admits against a predicted TTFT instead of raw queue depth — priority
classes, preemptive shedding of queued low-priority work, and
deadline-expired sheds before prefill; ``tools/loadgen.py`` is the
open-loop traffic source that exercises all of it.

``FLAGS_serving_disagg`` trades the symmetric replica set for a
*disaggregated* fleet (:class:`DisaggRouter` in disagg.py): P
prefill-only workers run the bucketed prompt pass and export each
request's committed KV blocks — an ownership-transfer record over the
paged pool — through a bounded handoff queue to D decode-only workers,
which splice the block table in for free when co-located on one
:class:`BlockPool` or copy the blocks across pools otherwise. Routing
is prefix-affine (``FLAGS_serving_prefix_affinity``): a fleet-wide
rolling-hash prefix index sends each request to the worker already
holding its longest cached prefix, so hit rates compound across the
fleet instead of fragmenting per replica. Same compiled steps, zero
extra XLA compiles, token-identical output.

Decoding is per-request *data* (decoding.py): every request carries a
:class:`DecodeParams` (temperature / top-k / top-p / stop sequences /
seed / json_mode) that the compiled steps consume as one fixed-shape
per-slot ``samp`` input — greedy, sampled, and JSON-grammar-constrained
rows mix freely in one batch of one executable, temp==0 rows stay
byte-identical to the pre-sampling engine, and speculative decoding
verifies sampled rows by rejection sampling. Multi-tenant LoRA
(lora.py) applies the block-table trick to weights: a paged
:class:`LoRAPool` of per-tenant low-rank factors rides the steps as
one more plain input, per-row adapter pages are gathered inside the
step, and loading/evicting adapters at runtime is a functional pool
write — zero new compiles for all of it
(``FLAGS_serving_lora_rank`` / ``FLAGS_serving_lora_max_adapters``).

Session capacity scales past HBM with the host-RAM KV tier
(kv_tier.py, ``FLAGS_serving_host_tier``): a fleet-shared
:class:`HostBlockStore` holds cold prefix chains int8-at-rest, a
:class:`TierManager` demotes idle chains between steps and promotes
them back on demand, and a :class:`SessionStore` lets
``submit(session=...)`` resume a demoted conversation
token-identically — concurrent sessions are bounded by host blocks,
not device blocks, and a system prompt is materialized once per
fleet.

See engine.py for the scheduler, kv_cache.py for the memory managers,
kv_tier.py for the host-RAM tier + session store, decoding.py for
sampling-as-data + the JSON grammar, lora.py for the paged adapter
pool, router.py for the symmetric replica front end, disagg.py for
the disaggregated fleet, http.py for the JSON front end.
"""

from .engine import QueueFullError, Request, ServingEngine
from .decoding import (DecodeParams, JsonGrammar, json_token_strings,
                       neutral_samp, request_key)
from .disagg import (DecodeEngine, DisaggRouter, HandoffQueue,
                     PrefillEngine)
from .http import ServingHTTPServer
from .kv_cache import (BlockAllocator, BlockKVCache, BlockPool,
                       prefix_chain_keys)
from .kv_tier import HostBlockStore, SessionStore, TierManager
from .lora import LoRAPool, make_adapter
from .router import AutoscalePolicy, ReplicaRouter

__all__ = ["ServingEngine", "Request", "QueueFullError",
           "BlockKVCache", "BlockAllocator",
           "BlockPool", "prefix_chain_keys",
           "HostBlockStore", "TierManager", "SessionStore",
           "ServingHTTPServer", "ReplicaRouter", "AutoscalePolicy",
           "DisaggRouter", "PrefillEngine", "DecodeEngine",
           "HandoffQueue",
           "DecodeParams", "JsonGrammar", "json_token_strings",
           "neutral_samp", "request_key",
           "LoRAPool", "make_adapter"]
