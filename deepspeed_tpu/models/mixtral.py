"""Mixtral-family sparse-MoE decoder (driver config 4: Mixtral-8x7B + EP).

Reference anchor: DeepSpeed trains Mixtral through MoE+ZeRO (``deepspeed/moe``
[K]; z3 leaf-module interplay for ``MixtralSparseMoeBlock`` [L ACC-DC:1148]);
its inference-v2 tree has a mixtral implementation [K].

TPU-first: Llama backbone (scan-over-layers, Ulysses attention) with the FFN
swapped for the GShard-dense MoE block — expert-stacked per-layer params
``[L, E, ...]`` sharded over the ``expert`` mesh axis, router aux loss
accumulated through the scan carry.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ..parallel.mesh import AXIS_EXPERT, AXIS_TENSOR
from .llama import LlamaConfig, LlamaModel

P = PartitionSpec


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 2.0
    aux_loss_coef: float = 0.02
    #: token dispatch rung for the MOELayer: auto | dense | sparse | pallas
    #: (ops/pallas/moe_dispatch.choose_dispatch_impl) — a tuning dimension
    moe_dispatch_impl: str = "auto"

    @classmethod
    def tiny(cls, **kw) -> "MixtralConfig":
        d = dict(vocab_size=512, hidden_size=128, intermediate_size=176,
                 num_layers=4, num_heads=8, num_kv_heads=4, max_seq_len=256,
                 num_experts=4, top_k=2)
        d.update(kw)
        return cls(**d)

    @classmethod
    def mixtral_8x7b(cls, **kw) -> "MixtralConfig":
        d = dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                 num_layers=32, num_heads=32, num_kv_heads=8,
                 max_seq_len=32768, rope_theta=1e6, num_experts=8, top_k=2)
        d.update(kw)
        return cls(**d)


class MixtralModel(LlamaModel):
    """Llama backbone + top-k routed SwiGLU experts."""

    def __init__(self, config: MixtralConfig, mesh: Any = None):
        super().__init__(config, mesh=mesh)
        self.aux_loss_coef = config.aux_loss_coef
        self._moe_layer = self._build_moe_layer()

    def _build_moe_layer(self) -> Any:
        """The routed FFN, called as ``(wg, experts, h) → (y, l_aux, meta)``:
        GShard's capacity gate here; a family with another routing rule
        (``OlmoeModel``) overrides this alone."""
        from ..moe.layer import swiglu_expert_fn
        from ..moe.sharded_moe import MOELayer, TopKGate

        config = self.config
        gate = TopKGate(num_experts=config.num_experts, k=config.top_k,
                        capacity_factor=config.capacity_factor,
                        eval_capacity_factor=config.capacity_factor,
                        min_capacity=4)
        expert_fn = partial(
            swiglu_expert_fn,
            constrain_act=lambda a: self._constrain(
                a, AXIS_EXPERT, None, AXIS_TENSOR))
        return MOELayer(gate, expert_fn, mesh=self.mesh,
                        dispatch_impl=config.moe_dispatch_impl)

    # ------------------------------------------------------------------

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        c = self.config
        params = super().init_params(rng)
        L, E, H, I = c.num_layers, c.num_experts, c.hidden_size, \
            c.intermediate_size
        k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(rng, 17), 4)

        def normal(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    / np.sqrt(fan_in)).astype(jnp.float32)

        # replace the dense MLP with router + expert-stacked FFN
        del params["layers"]["mlp"]
        params["layers"]["moe"] = {
            "wg": normal(k1, (L, H, E), H),
            "w_gate": normal(k2, (L, E, H, I), H),
            "w_up": normal(k3, (L, E, H, I), H),
            "w_down": normal(k4, (L, E, I, H), I),
        }
        return params

    def param_specs(self, params: Optional[Any] = None) -> Dict[str, Any]:
        specs = super().param_specs(params)
        e, t = AXIS_EXPERT, AXIS_TENSOR
        from ..parallel.mesh import AXIS_PIPE

        pipe = (AXIS_PIPE if self.mesh is not None
                and int(self.mesh.shape.get(AXIS_PIPE, 1)) > 1 else None)
        del specs["layers"]["mlp"]
        specs["layers"]["moe"] = {
            "wg": P(pipe, None, None),
            "w_gate": P(pipe, e, None, t),
            "w_up": P(pipe, e, None, t),
            "w_down": P(pipe, e, t, None),
        }
        return specs

    # ------------------------------------------------------------------

    def _ffn(self, h: jnp.ndarray, lp: Any, layer: Any = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Routed-FFN via the shared MOELayer (one dispatch implementation
        for the whole framework) with an expert-TP-constrained SwiGLU expert.
        ``layer`` is passed through to a layer object that takes the expert
        stacks whole (``DroplessMoE``): ``lp["moe"]``'s ``w_*`` are then the
        ``[L, E, …]`` stacks, ``wg`` still the one layer's."""
        from ..telemetry import numerics

        moe = lp["moe"]
        y, l_aux, meta = self._moe_layer(
            moe["wg"], {k: moe[k] for k in ("w_gate", "w_up", "w_down")}, h,
            **({} if layer is None else {"layer": layer}))
        numerics.moe_stats(meta)
        return numerics.probe("mlp_out", y), l_aux
