"""Timestep-boundary expert switching — the WAN2.2 A14B two-expert denoiser.

WAN2.2's 14B release splits denoising between two full DiT checkpoints: a
high-noise expert for early steps and a low-noise expert for the rest, switched
at a fixed flow-time boundary. In a ComfyUI graph nothing here is needed: the
template wires two ``KSamplerAdvanced`` nodes, one an expert, and the step
windows do the switch (nodes.TPUKSamplerAdvanced; the benchmark's
``wan22-t2v-a14b`` cell runs that graph). This class is the same selection for
code that drives ONE sampler over both experts — ``pipelines.WanVideoPipeline``
and library users — where the host app of the reference picks the model per
step and the wrapper only patches whichever forward it is given
(any_device_parallel.py:1450-1451).

Design: the samplers are host-side loops (sampling/ddim.py docstring) whose
timestep values are concrete at each call, so the switch is plain Python — no
`lax.cond` over two 14B parameter sets (which would force both experts resident
in one program). Each expert can be `parallelize`d independently, and each keeps
its own compiled programs; the boundary never recompiles anything.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

# Official WAN2.2 A14B switch points (flow time in [0, 1]).
WAN22_T2V_BOUNDARY = 0.875
WAN22_I2V_BOUNDARY = 0.900


@dataclasses.dataclass
class TimestepExpertSwitch:
    """Callable denoiser that routes each step to one of two experts by the
    step's flow time: ``t >= boundary`` → ``high_noise``, else ``low_noise``.

    Timestep units follow the sampler driving it (flow samplers pass t ∈ [0, 1];
    pass a boundary in the same units if driving with another family). Both
    experts may be bare DiffusionModels or ParallelModels — parallelize them
    separately, with different chains if desired.
    """

    high_noise: Any
    low_noise: Any
    boundary: float = WAN22_T2V_BOUNDARY

    def expert_for(self, timesteps) -> Any:
        t = float(jnp.max(jnp.asarray(timesteps)))
        return self.high_noise if t >= self.boundary else self.low_noise

    def __call__(self, x, timesteps, context=None, **kwargs):
        return self.expert_for(timesteps)(x, timesteps, context, **kwargs)

    @property
    def model_config(self):
        from ..parallel.orchestrator import model_config_of

        return model_config_of(self.high_noise)

    def cleanup(self) -> None:
        for expert in (self.high_noise, self.low_noise):
            fn = getattr(expert, "cleanup", None)
            if fn is not None:
                fn()
