"""OLMoE-family sparse-expert decoder (Muennighoff et al., arXiv
2409.02060; ``allenai/OLMoE-1B-7B``): the Llama backbone with

* a q/k RMSNorm over the whole projection (``LlamaConfig.qk_norm``), and
* a **dropless** top-k routed SwiGLU FFN: ``p = softmax_f32(h·Wg)`` over
  all experts, the ``top_k`` largest used as they are (``norm_topk_prob``
  false: they sum to well under 1) or renormalised, every assignment
  computed whatever the batch holds (``moe.layer.DroplessMoE`` on
  ``ops/pallas/moe_grouped_matmul``).

Weights sit in Mixtral's stacked layout (``layers.moe.{wg [L,H,E], w_gate,
w_up [L,E,H,I], w_down [L,E,I,H]}``) plus ``layers.attn.q_norm`` / ``k_norm
[L, heads·d]``; init, partition specs and the ``_ffn`` hook are
``MixtralModel``'s, the routing rule is this family's own.

The grouped matmul takes the expert stacks ``[L, E, …]`` plus a layer's
index and reads the layer where it lies.  The training scan of
``models/llama.py`` slices every leaf a layer a step (XLA's own matmuls
fuse that slice), so there ``_ffn`` hands ``DroplessMoE`` one layer's
leaves and it makes the stack of one.  The serving engine's
``OlmoeV2Adapter`` (``inference/v2/adapters.py``) keeps the three stacks
out of its scan and passes them whole with ``layer=l``: sliced, each
layer's 805 MB of experts was copied for the Mosaic call.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from .llama import LlamaConfig
from .mixtral import MixtralModel


@dataclasses.dataclass(frozen=True)
class OlmoeConfig(LlamaConfig):
    qk_norm: bool = True
    num_experts: int = 64
    top_k: int = 8
    #: the published ``norm_topk_prob``: divide the k weights by their sum
    norm_topk_prob: bool = False
    #: weight of the router's load-balancing loss in ``loss``
    aux_loss_coef: float = 0.01

    @classmethod
    def tiny(cls, **kw) -> "OlmoeConfig":
        d = dict(vocab_size=512, hidden_size=128, intermediate_size=128,
                 num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=256,
                 num_experts=8, top_k=3)
        d.update(kw)
        return cls(**d)


class OlmoeModel(MixtralModel):
    """Llama backbone + q/k norm + dropless top-k routed SwiGLU experts."""

    def _build_moe_layer(self) -> Any:
        from ..moe.layer import DroplessMoE

        c = self.config
        return DroplessMoE(c.num_experts, c.top_k,
                           renormalize=c.norm_topk_prob, mesh=self.mesh)
