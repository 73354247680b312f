"""deepseek-v3 — 256 routed experts top-8 and 1 shared, sigmoid scores,
node-limited routing (4 of 8 expert groups), first 3 layers dense
[arXiv:2412.19437; hf deepseek-ai/DeepSeek-V3 config.json].

Only the MoE traffic (:mod:`repro.workloads.moe`) uses this config.  Its
attention is MLA (``kv_lora_rank`` 512, ``qk_rope_head_dim`` 64), which
:mod:`repro.nn` does not have, so the config is not in ``ARCH_IDS`` and no
model is built from it; the head fields give the published head counts and
the value head width.
"""
from repro.nn.config import ArchConfig

ARCH_ID = "deepseek-v3"


def config() -> ArchConfig:
    return ArchConfig(
        name=ARCH_ID, family="moe",
        n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128,
        d_ff=18432,                      # the 3 leading dense layers' FFN
        vocab_size=129280,
        d_head=128, rope_theta=10000.0,
        n_experts=256, n_experts_active=8, n_shared_experts=1,
        moe_d_ff=2048, first_dense_layers=3,
        scoring_func="sigmoid", n_group=8, topk_group=4,
    )
