"""Hot-path markers for dynalint (DT004/DT005).

A *hot path* is a function on the per-token serving critical path: the
engine tick loop, prefill/decode step assembly, sampling, and the
paged-attention callers.  Inside these, an accidental host-device sync
(``np.asarray`` on a device array, ``jax.device_get``,
``.block_until_ready()``) serializes the software-pipelined device queue
behind a full device->host round trip, and a ``jnp.asarray`` over a
request-shaped Python list is a recompile hazard.  dynalint's DT004/DT005
rules scan exactly the functions marked here.

Two ways to mark a function:

* decorate it with :func:`hot_path` -- preferred for code this package owns
  (the decorator is a pure annotation: it tags and returns the SAME function
  object, so ``jax.jit``, ``functools.partial`` introspection and pickling
  are unaffected);
* list it in :data:`HOT_PATH_MANIFEST` -- for modules where editing every
  function is churn (e.g. the jitted step/kernel files whose whole surface
  is hot).  Keys are module-path suffixes (``/``-separated), values are
  ``fnmatch`` patterns over function qualnames.

This module must stay import-light (no jax/numpy): engine modules import
the decorator, and the analyzer imports the manifest.
"""

from __future__ import annotations

from typing import Callable, Dict, List, TypeVar

F = TypeVar("F", bound=Callable)

HOT_PATH_ATTR = "__dynalint_hot_path__"

# module-path suffix -> qualname fnmatch patterns.  Every function matching
# a pattern in a matching module is analyzed as a hot path.
HOT_PATH_MANIFEST: Dict[str, List[str]] = {
    # the whole jitted step-assembly surface is hot: everything here runs
    # under jax.jit inside the tick loop's dispatch.  The ``_``-prefixed
    # names are the raw implementations behind the module-level jit
    # wrappers (``decode_block = partial(jax.jit, ...)(_decode_block)``)
    # -- the serving-mesh path re-jits exactly these with explicit in/out
    # shardings (parallel/sharding.make_sharded_steps), so their BODIES
    # are the hot surface DT004/DT005 must scan
    "dynamo_tpu/engine/step.py": [
        "decode_step",
        "_decode_once",
        "decode_block",
        "_decode_block",
        "packed_unified_step",
        "_packed_unified_step",
        "packed_unified_multistep",
        "_packed_unified_multistep",
        "_mixed_sample_epilogue",
        "_spec_columns_epilogue",
        "verify_and_sample",
        "_verify_and_sample",
        "score_prompt_step",
        "prefill_step",
        "prefill_and_sample",
        "prefill_mm_and_sample",
        "prefill_suffix_and_sample",
        "sample_step",
        "sample_step_packed",
        "embed_step",
        "update_lanes",
        "_update_lanes",
        "inject_token",
        "_inject_token",
        "inject_tokens",
        "_inject_tokens",
        "zero_count_rows",
        "_zero_count_rows",
        "bump_counts",
        "_bump_counts",
        "seed_count_rows",
        "_seed_count_rows",
    ],
    # the jitted page movers of the KV blob: eviction snapshots, tier and
    # swap restores, and the layer-page gather/scatter of the chunked KV
    # delivery on the tick loop
    "dynamo_tpu/engine/kv_cache.py": [
        "scatter_block_pages",
        "_scatter_block_pages",
        "slice_block_pages",
        "_slice_block_pages",
        "gather_layer_pages",
        "_gather_layer_pages",
        "scatter_layer_pages",
        "_scatter_layer_pages",
    ],
    # multichip serving entry points: the sharded re-jit factory (its jit
    # wrappers pin in/out shardings over the raw step bodies above --
    # DT011 separately enforces the declarations) and the sp/pp prefill
    # routes the sharded engine dispatches long prompts through
    "dynamo_tpu/parallel/sharding.py": [
        "make_sharded_steps",
        "make_sharded_drafter",
    ],
    "dynamo_tpu/parallel/pipeline_parallel.py": [
        "pp_prefill_step",
    ],
    "dynamo_tpu/parallel/ring_attention.py": [
        "ring_attention_chunk",
        "ring_prefill_step",
        "make_ring_attention",
    ],
    # paged-attention kernels
    "dynamo_tpu/ops/paged_attention.py": [
        "paged_decode_attention*",
    ],
    # flash prefill kernels (full-prompt and prefix-suffix)
    "dynamo_tpu/ops/flash_prefill.py": [
        "flash_prefill_attention",
        "flash_prefix_prefill_attention",
    ],
    # the unified mixed prefill+decode ragged kernels over the packed
    # token axis: the ONE attention call of step.packed_unified_step,
    # dispatched every tick under mixed batching (the *_xla references
    # are the same entry point's CPU path), and the work-list kernel's
    # other entry, the decode launch of the fused steps over a dense pool
    "dynamo_tpu/ops/ragged_attention.py": [
        "ragged_paged_attention_xla",
        "packed_ragged_attention*",
        "_packed_kernel",
        "decode_work_list_attention",
        "_work_list_launch",
        "_work_list_kernel",
    ],
    # the latent pool's kernels (MLA): the attention call of the packed
    # step and of the fused decode steps over a kv_cache.LatentKV
    "dynamo_tpu/ops/latent_attention.py": [
        "latent_packed_attention",
        "latent_decode_attention",
        "packed_work_list",
        "_launch",
        "_latent_kernel",
    ],
    # the dropless expert MLP's grouped product, three launches a layer of
    # every mixed step of a no-drop MoE engine (model._moe_grouped)
    "dynamo_tpu/ops/grouped_matmul.py": [
        "grouped_matmul",
        "_grouped_matmul_pallas",
        "group_visits",
        "_kernel",
    ],
    # the gated delta rule's chunks, one launch a linear layer of every
    # packed step of a trunk that has such layers (attention.packed_delta_mix)
    "dynamo_tpu/ops/gated_delta.py": [
        "gated_delta_chunks",
        "_kernel",
    ],
    # offload-plane hot paths: the admission-time tier lookup runs on the
    # event loop and the host-ring put sits behind every eviction -- a
    # host sync or recompile hazard in these stalls admission or the
    # offload thread's drain rate (DT009 separately forbids sync
    # device<->host transfers module-wide outside COPY_HELPERS)
    "dynamo_tpu/offload.py": [
        "HostTier.put",
        "HostTier.get_ram",
        "KVOffloadEngine.lookup",
        "KVOffloadEngine.submit_evict",
        "KVOffloadEngine.swap_out",
    ],
    # speculative-decoding hot paths: drafting runs on the engine executor
    # once per verify dispatch and sits on the per-step critical path for
    # every speculating lane -- a host sync or recompile hazard there
    # stalls the whole verify cadence (engine._dispatch_verify and the
    # verify/score steps are separately marked with @hot_path)
    "dynamo_tpu/spec/drafter.py": [
        "NGramDrafter.propose",
        "longest_accepted",
    ],
    # model-based drafter (ISSUE 15): the jitted greedy draft forward is
    # hot like every other step body (DT010 covers spec/ modules too).
    # ModelDrafter.propose itself is deliberately NOT marked: it performs
    # the drafter's one designed host sync (fetching the proposed token
    # ids), and the engine keeps that sync off the dispatch-assembly path
    # via the commit-time precompute (SpecState.pending_draft).
    "dynamo_tpu/spec/model_drafter.py": [
        "draft_greedy_tokens",
        "_draft_greedy_tokens",
    ],
}


def hot_path(fn: F) -> F:
    """Mark ``fn`` as serving-critical for dynalint DT004/DT005.

    Returns ``fn`` itself (tagged, not wrapped): safe above/below
    ``jax.jit`` and any decorator that inspects the function object.
    """
    try:
        setattr(fn, HOT_PATH_ATTR, True)
    except (AttributeError, TypeError):  # builtins / slotted callables
        pass
    return fn
