"""Layered continuous-batching serving stack.

Four public layers, vLLM/SGLang-style, over one device-resident core:

* ``SamplingParams`` (``repro.serving.sampling``) — greedy / temperature /
  top-k / top-p with a per-request seed, fused into the donated decode
  step.
* ``Scheduler`` / ``PreemptionPolicy`` (``repro.serving.scheduler``) —
  pluggable admission order (FCFS / priority / SJF) and eviction policy
  (youngest-victim swap / recompute).
* ``CacheManager`` (``repro.serving.cache_manager``) — the contiguous and
  paged KV layouts behind one alloc/write/grow/evict/restore surface.
* ``LLMEngine`` (``repro.serving.api``) — ``generate()`` / ``stream()``
  facade over the engine.
* ``ChaosInjector`` (``repro.serving.chaos``) — deterministic fault
  injection (device faults, pool exhaustion, corrupt readbacks, stalls,
  aborts) for exercising the request-lifecycle robustness layer.
* ``SpecConfig`` (``repro.serving.spec``) — speculative decoding fused
  into the device-resident step: an n-gram or small-draft-model drafter
  proposes ``k`` tokens, the target verifies all ``k + 1`` positions in
  one program, rejected KV writes land on the trap page; greedy streams
  stay bit-identical to target-only decoding.

``Engine`` is the execution core; ``ReferenceEngine`` is the host-driven
loop it is proven bit-identical against (greedy FCFS).
"""

from repro.serving.api import LLMEngine, RequestOutput, TokenEvent
from repro.serving.cache_manager import (CacheConfig, CacheManager,
                                         ContiguousCacheManager,
                                         PagedCacheManager)
from repro.serving.chaos import ChaosInjector, InjectedDeviceFault
from repro.serving.engine import Engine, ProgramError, Request
from repro.serving.reference import ReferenceEngine
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import (FCFSScheduler, PreemptionPolicy,
                                     PriorityScheduler, RecomputePreemption,
                                     Scheduler, SJFScheduler,
                                     SwapPreemption, make_preemption,
                                     make_scheduler)
from repro.serving.spec import (DraftModelDrafter, Drafter, NGramDrafter,
                                SpecConfig)

__all__ = [
    "CacheConfig", "CacheManager", "ChaosInjector",
    "ContiguousCacheManager", "DraftModelDrafter", "Drafter", "Engine",
    "FCFSScheduler", "InjectedDeviceFault", "LLMEngine",
    "NGramDrafter", "PagedCacheManager", "PreemptionPolicy",
    "ProgramError",
    "PriorityScheduler", "RecomputePreemption", "ReferenceEngine",
    "Request", "RequestOutput", "SJFScheduler", "SamplingParams",
    "Scheduler", "SpecConfig", "SwapPreemption", "TokenEvent",
    "make_preemption", "make_scheduler",
]
