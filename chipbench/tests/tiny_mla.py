"""The deepseek-v2-lite config file cut to CPU size: the same cut in the
file's stated sizes and in the program's overrides. Three layers (one
dense, two expert layers) and a share of 4 of 8 experts from expert 2,
with 3 experts a token, so some picks are held elsewhere."""
from __future__ import annotations

import json

from chipbench import registry

SMALL = {"num_hidden_layers": 3, "hidden_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "intermediate_size": 256,
         "moe_intermediate_size": 64, "n_routed_experts": 4,
         "routed_experts_published": 8, "routed_experts_offset": 2,
         "num_experts_per_tok": 3, "n_shared_experts": 1}
TO_PROGRAM = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads",
              "kv_lora_rank": "kv_lora_rank",
              "qk_nope_head_dim": "qk_nope_head_dim",
              "qk_rope_head_dim": "qk_rope_head_dim",
              "v_head_dim": "v_head_dim", "intermediate_size": "dense_d_ff",
              "moe_intermediate_size": "d_ff",
              "n_routed_experts": "n_experts_held",
              "routed_experts_published": "n_experts",
              "routed_experts_offset": "expert_offset",
              "num_experts_per_tok": "top_k",
              "n_shared_experts": "n_shared_experts",
              "vocab_size": "vocab_size"}


def small_model(vocab: int = 512, **cut) -> dict:
    model = json.loads((registry.PKG / "configs" /
                        "deepseek-v2-lite.json").read_text())
    model.update(SMALL, vocab_size=vocab, **cut)
    over = dict(model["overrides"])
    over.update({TO_PROGRAM[k]: model[k] for k in TO_PROGRAM})
    over["head_dim"] = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    model["overrides"] = over
    return model
