"""The model handle the orchestrator consumes.

The reference duck-types ComfyUI's MODEL wrapper down to a bare ``diffusion_model``
with ``forward(x, timesteps, context=None, **kwargs)`` (any_device_parallel.py:921-930,
1287). The functional analogue is this dataclass: a pure ``apply`` + ``params`` pytree
+ metadata the parallel layers need (block lists for pipeline placement, preferred
dtype). ``parallelize`` accepts it directly (it satisfies the .apply/.params protocol).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from ..utils import tracing
from ..utils.metrics import registry


def denoise_span(program: str, x):
    """Count one denoiser forward of an eager sampler loop and bracket the
    host's dispatch of it. Every such forward passes one of two sites:
    ``DiffusionModel.__call__`` and, for a wrapped model,
    ``ParallelModel.__call__`` (a ControlNet composition is one
    ``DiffusionModel`` and passes once). The counter is always on: scraped
    from ``/metrics`` its rate is forwards per second per program, and its
    increase over a prompt holds a server without the tracer to its graph's
    ``steps``; the ``denoise`` span nests under the sampler's ``step``. The
    whole-loop program (``compile_loop=True``) and the serving lanes call
    ``apply`` inside programs of their own and pass neither site."""
    registry.counter(
        "pa_denoiser_calls_total", labels={"program": program},
        help="denoiser forwards dispatched by the eager sampler loops "
             "(DiffusionModel / ParallelModel calls)",
    )
    return tracing.span("denoise", cat="sampling", program=program,
                        rows=x.shape[0] if hasattr(x, "shape") else None)


@dataclasses.dataclass(frozen=True)
class PipelineSegment:
    """One pipeline-schedulable unit of the forward pass — usually a single block of a
    block list (the things the reference wraps in ParallelBlock, 1180-1198).

    ``param_keys`` names the top-level entries of the parameter pytree this segment
    reads, so the pipeline runner can place exactly that sub-pytree on the owning
    device. ``fn(params, carry) -> carry`` runs the segment; ``carry`` is a flat dict
    of arrays with a stable structure across every segment of the model, so stage
    programs compose and activations hop devices as one pytree.
    """

    param_keys: tuple[str, ...]
    fn: Callable[[Any, dict], dict]
    label: str = ""


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    """A model's pipeline decomposition: prepare (lead) → segments (staged) → finalize
    (lead). The functional analogue of the reference's block-list walk + ParallelBlock
    wrapping (any_device_parallel.py:1152-1198): non-block layers (embeddings, final
    norm/projection) always run on the lead device (SURVEY §3.4), block segments are
    assigned contiguous ranges proportional to device weights.
    """

    prepare_keys: tuple[str, ...]
    prepare: Callable[..., dict]  # (params, x, t, context, **kwargs) -> carry
    segments: tuple[PipelineSegment, ...]
    finalize_keys: tuple[str, ...]
    # (params, carry, out_shape) -> output; out_shape is the original input's shape
    # tuple (static at trace time), so the head can recover un-patchify geometry
    # without dragging the input array itself across devices.
    finalize: Callable[[Any, dict, tuple], Any]


@dataclasses.dataclass
class DiffusionModel:
    """A diffusion network as data: pure apply fn + weights + metadata."""

    apply: Callable[..., Any]
    params: Any
    name: str = "model"
    config: Any = None
    # Pipeline metadata — the analogue of the reference's block-list discovery over
    # ['double_blocks', 'single_blocks', 'transformer_blocks', 'layers'] (1156):
    # maps block-list name -> number of blocks, in execution order.
    block_lists: dict[str, int] | None = None
    # Staged decomposition for the batch==1 pipeline mode; None → model cannot
    # pipeline and the router falls back to single-device (parity: no known block
    # list found, 1156-1166).
    pipeline_spec: PipelineSpec | None = None
    # Model-level sampling preferences set by patch nodes (the host's
    # model_options analogue): e.g. {"cfg_rescale": 0.7} from RescaleCFG.
    # Samplers read these as defaults; explicit widget values win.
    sampler_prefs: dict | None = None
    # Loader provenance ({"path", "family"}, set by the checkpoint loaders):
    # the LoraLoader shims re-bake from the ORIGINAL file, so this must
    # survive every patch node's dataclasses.replace — hence a field, not an
    # object.__setattr__ side channel.
    source: dict | None = None
    # Serving delegation for ControlNet compositions (models/controlnet.
    # apply_control): {"base", "ctrl_apply", "ctrl_params", "hint",
    # "strength", "start", "end"}. The continuous-batching scheduler buckets
    # such a model on its BASE, carrying the control net as per-lane state —
    # so ControlNet traffic co-batches with plain txt2img instead of each
    # composition getting a private bucket. None → serve as an opaque model.
    control_delegate: dict | None = None
    # Serving delegation for baked-LoRA models (the LoraLoader shims): the
    # {"base", "factors"} pair behind this bake — ``base`` is the UNPATCHED
    # model object (the checkpoint loader's cached output, so identity
    # matches plain-traffic prompts) and ``factors`` the extracted
    # {param_path: (a, b)} map with strength pre-folded. Samplers that see
    # this submit (base, factors) to the serving tier so per-request LoRA
    # rides as per-lane state (one shared program, any LoRA mix); inline
    # legs keep using THIS model's baked params. None → bake only.
    lora_delegate: dict | None = None

    def __call__(self, x, timesteps, context=None, **kwargs):
        """Jit-compiled forward (cached per shape and per ambient sequence_parallel
        context — the ctx is read at trace time inside ops.attention); kwargs must be
        arrays here — route python-valued kwargs through ``apply`` directly."""
        from ..ops.attention import sequence_ctx_key

        if not hasattr(self, "_jit_cache"):
            object.__setattr__(self, "_jit_cache", {})
        key = sequence_ctx_key()
        fn = self._jit_cache.get(key)
        if fn is None:
            from ..utils.telemetry import instrument_jit

            # palint: allow[recompile-hazard] one name per LOADED MODEL
            # (bounded; per-model compile attribution is the point)
            fn = self._jit_cache[key] = instrument_jit(
                self.apply, f"model-apply:{self.name}"
            )
        with denoise_span(fn.name, x):
            return fn(self.params, x, timesteps, context, **kwargs)

    def n_params(self) -> int:
        import jax

        return sum(int(l.size) for l in jax.tree.leaves(self.params))
