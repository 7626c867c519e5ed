"""GPTMoE: the GPT family with mixture-of-experts FFN blocks.

Same embedding/attention/LN skeleton as `models/gpt.py` (the blocks are
built through GPTModel's `block_cls` hook, so cache/remat/sequence-
parallel plumbing is inherited, not copied); every block's dense MLP is
replaced by a routed `MoEFFN`. The training loss folds in the router's
load-balancing aux loss and z-loss, and the per-step routing health
rides the telemetry step record (`collect_moe_stats` — consumed by
TrainStep/ShardedTrainStep as a device-side aux output).

The planner sees this family through `gpt_moe_abstract_params` (name/
shape/dtype parity with the live model, pinned by a test) and
`planner.rules.gpt_moe_partition_rules` (experts sharded over ep).
"""
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..models.gpt import (GPTBlock, GPTConfig, GPTForPretraining,
                          GPTModel)
from .layer import MoEFFN
from .router import STATS_FIELDS

__all__ = ["GPTMoEConfig", "GPTMoEBlock", "GPTMoEModel", "GPTMoE",
           "gpt_moe_tiny_config"]


class GPTMoEConfig(GPTConfig):
    """GPTConfig + MoE knobs. `num_experts` > 0 is what the planner's
    layout enumeration keys on to open the ep axis."""

    def __init__(self, num_experts=8, expert_top_k=2,
                 capacity_factor=1.25, aux_loss_weight=0.01,
                 z_loss_weight=1e-3, **kw):
        super().__init__(**kw)
        self.num_experts = int(num_experts)
        self.expert_top_k = int(expert_top_k)
        self.capacity_factor = float(capacity_factor)
        self.aux_loss_weight = float(aux_loss_weight)
        self.z_loss_weight = float(z_loss_weight)


class GPTMoEBlock(GPTBlock):
    """GPTBlock with the dense MLP swapped for the routed MoEFFN via
    the mlp_cls factory hook. Everything else — forward, cache,
    fused-ln — is inherited unchanged, so attention numerics can never
    drift from the dense family."""

    mlp_cls = MoEFFN
    ffn_scope = "experts"


class GPTMoEModel(GPTModel):
    block_cls = GPTMoEBlock


class GPTMoE(GPTForPretraining):
    """GPT pretraining head over MoE blocks. loss() = LM loss +
    aux_loss_weight * mean-over-layers aux + z_loss_weight * z."""

    model_cls = GPTMoEModel

    @property
    def moe_num_experts(self):
        return self.config.num_experts

    def _moe_layers(self):
        return [b.mlp for b in self.gpt.blocks
                if isinstance(b.mlp, MoEFFN)]

    def loss(self, input_ids, labels, loss_mask=None):
        lm = super().loss(input_ids, labels, loss_mask)
        auxes = [m.aux_loss() for m in self._moe_layers()]
        zs = [m.z_loss() for m in self._moe_layers()]
        if not auxes or auxes[0] is None:
            return lm
        c = self.config
        n = float(len(auxes))
        aux = sum(auxes[1:], auxes[0]) * (1.0 / n)
        z = sum(zs[1:], zs[0]) * (1.0 / n)
        return lm + c.aux_loss_weight * aux + c.z_loss_weight * z

    def collect_moe_stats(self):
        """Mean routing-health vector over the MoE layers of the LAST
        forward as a raw jnp (5,) array (router.STATS_FIELDS order) —
        the trainers return it as a device-side aux output of the
        compiled step and note it into the telemetry record. None
        before any forward ran."""
        stats = [m.stats() for m in self._moe_layers()]
        if not stats or stats[0] is None:
            return None
        vals = [s._value if isinstance(s, Tensor) else jnp.asarray(s)
                for s in stats]
        return sum(vals[1:], vals[0]) / float(len(vals))


def gpt_moe_tiny_config(**kw):
    """Small MoE config for tests/dryrun/graphdoctor (mirrors
    models.gpt.gpt_tiny_config; E=4 experts keeps every ep<=4 mesh
    factorization reachable)."""
    defaults = dict(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0,
                    num_experts=4, expert_top_k=2, capacity_factor=2.0,
                    use_flash_attention=False)
    defaults.update(kw)
    return GPTMoEConfig(**defaults)


# STATS_FIELDS re-export for the telemetry wiring
MOE_STATS_FIELDS = STATS_FIELDS
