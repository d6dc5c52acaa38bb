"""Continuous-batching serving engine with a paged KV cache.

The ROADMAP north star serves heavy traffic from millions of users; the
static path (inference/serving.BatchingServer over Predictor buckets)
groups one-shot predicts, but generation workloads are RAGGED — every
request has its own prompt length, output length, arrival time, and
deadline. This package is the TPU-native answer:

- kv_cache.py   — PagedKVCache block pool + block tables +
                  paged_attention (dispatches to the Pallas ragged
                  paged attention kernel, ops/pallas/paged.py, with the
                  pure-JAX reference as documented fallback —
                  PADDLE_TPU_PAGED_KERNEL=0/1/auto) + dense-interface
                  adapters for inference/decoding.py step_fns; with
                  `kv_dtype="int8"` the pools store int8 codes + per-
                  row f32 scales (quantize at write, dequant fused
                  into the kernel's gather — ~2x blocks per chip,
                  docs/serving.md "Quantized serving");
- scheduler.py  — iteration-level continuous batching: fixed decode
                  slots, chunked prefill admission, EOS/length
                  retirement, watermark backpressure, priorities,
                  deadlines (injectable clock);
- engine.py     — GenerationServer: one jitted fused prefill/decode
                  step for the server lifetime, submit/Future surface,
                  streaming token callbacks, graceful drain; with
                  `mesh=` the pools shard over the head axis and the
                  fused step runs under shard_map (one psum per
                  sub-block, scheduler state replicated on the host —
                  docs/serving.md "Serving on a mesh");
- model.py      — ServingModel, the engine's view of a model: a family
                  is a config, `init_params` and a spec, from which
                  the facts, the cache's geometry and the names of the
                  step's counts are derived; GPTServingModel adds int8
                  weights and the tensor-parallel step. The families:
                  latent_moe.py, linear_moe.py, smallthinker.py,
                  brumby.py;
- step.py / blocks.py — the ONE fused step every model runs, and the
                  kinds it is made of (norms, attentions with their
                  cache geometry, MLPs) layer by layer
                  (docs/serving.md "Model families: the block is a
                  spec");
- prefix_cache.py — cross-request KV block sharing: content-hash-chain
                  index over prompt chunks, refcounted blocks,
                  copy-on-write on divergence, LRU eviction under
                  watermark pressure (`prefix_cache=True`);
- spec_decode.py — speculative decoding: a draft model proposes k
                  tokens, the fused step verifies them in one chunked
                  call, greedy acceptance is bitwise-exact
                  (`spec=SpecDecodeConfig(draft_model, k)`);
- replica.py    — one GenerationServer behind the fleet lifecycle
                  contract (health/load/affinity probes, drain, kill);
- transport.py / worker.py / remote.py — the out-of-process backend:
                  a length-prefixed localhost-socket RPC (versioned
                  frames, JSON header + raw tensor blobs), the worker
                  process serving a GenerationServer behind it, and
                  the parent-side WorkerProxy speaking the engine
                  surface — `make_subprocess_spawn(...)` turns a
                  checkpoint dir into a spawn_fn whose replicas are
                  real processes (real SIGKILL chaos, SLO-driven
                  autoscaling via `autoscale=`; docs/serving.md
                  "Out-of-process fleet");
- decode_strategies.py / guided.py — COW-forked generation on the
                  shared KV cache: `submit(n=K)` / `SamplingParams`
                  fork K sampling lanes that alias the prompt's blocks
                  (refcounts, copy-on-write on divergence),
                  `BeamParams` runs paged beam search bitwise-identical
                  to the dense `beam_search` epilogue, and `guided=`
                  (RegexConstraint / ChoiceConstraint / JsonConstraint)
                  masks the fused step's sampling path with a
                  host-automaton token mask (docs/serving.md "Forked
                  generation & guided decoding");
- router.py     — FleetRouter: N replicas behind one submit() —
                  prefix-affinity routing (the index chain keys ARE
                  the affinity signal), SLO-burn-rate admission
                  control (AdmissionRejected + retry-after), failover
                  re-admission with stream dedupe, and a disaggregated
                  prefill/decode RouterPolicy whose KV handoff is a
                  cross-replica pool-slice transfer
                  (docs/serving.md "Fleet serving"); with
                  `supervisor=`/`spawn_fn=` the fleet SELF-HEALS —
                  hung-replica watchdog, replica resurrection under a
                  crash-loop breaker with prefix re-warm, and
                  poison-request quarantine
                  (robustness/supervisor.py, docs/robustness.md
                  "Self-healing fleet").

Entry points: `GenerationServer(GPTServingModel.from_scope(scope, cfg))`
directly, or `AnalysisConfig.enable_generation(...)` +
`Predictor.generation_server()` from a saved model dir. docs/serving.md
has the block-table layout and tuning guide.
"""

from .kv_cache import (NULL_BLOCK, PagedDecodeLayer, PagedKVCache,
                       build_paged_decode_cache, fuse_kv,
                       gather_block_kv, paged_attention,
                       paged_attention_reference, split_kv)
from .prefix_cache import PrefixCacheIndex, prompt_chain_keys
from .scheduler import (ContinuousBatchingScheduler, DeadlineExceeded,
                        GenerationResult, RequestCancelled)
from .decode_strategies import (BeamHypothesis, BeamParams, GroupFuture,
                                GroupResult, SamplingParams)
from .guided import (ChoiceConstraint, Constraint, JsonConstraint,
                     RegexConstraint)
from .model import GPTServingModel, ServingModel
from .engine import GenerationFuture, GenerationServer
from .latent_moe import LatentMoEServingModel
from .linear_moe import LinearMoEServingModel
from .smallthinker import SmallThinkerServingModel
from .brumby import BrumbyServingModel
from .spec_decode import SpecDecodeConfig
from .replica import Replica
from .router import (AdmissionPolicy, AdmissionRejected, FleetFuture,
                     FleetRouter, RouterPolicy)
from .transport import (FrameError, RemoteError, RpcTimeout,
                        TransportError, VersionMismatch)
from .remote import WorkerProxy, make_subprocess_spawn, spawn_worker

__all__ = [
    "PagedKVCache", "PagedDecodeLayer", "paged_attention",
    "paged_attention_reference", "gather_block_kv", "fuse_kv",
    "split_kv", "build_paged_decode_cache", "NULL_BLOCK",
    "PrefixCacheIndex", "prompt_chain_keys", "SpecDecodeConfig",
    "SamplingParams", "BeamParams", "BeamHypothesis", "GroupResult",
    "GroupFuture", "Constraint", "RegexConstraint", "ChoiceConstraint",
    "JsonConstraint",
    "ContinuousBatchingScheduler", "GenerationResult",
    "DeadlineExceeded", "RequestCancelled",
    "GenerationServer", "GenerationFuture", "ServingModel",
    "GPTServingModel",
    "LatentMoEServingModel", "LinearMoEServingModel",
    "SmallThinkerServingModel", "BrumbyServingModel",
    "Replica", "FleetRouter", "FleetFuture", "RouterPolicy",
    "AdmissionPolicy", "AdmissionRejected",
    "WorkerProxy", "make_subprocess_spawn", "spawn_worker",
    "TransportError", "FrameError", "VersionMismatch", "RpcTimeout",
    "RemoteError",
]
