"""Small CPU sizes of the configurations added after
``tests/rehearsal.py``'s ``SMALL`` was written, put into it before any
test runs, so that ``rehearsal.make_root`` shrinks every configuration
that ``BENCHMARK.json`` names."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from chipbench.tests import rehearsal  # noqa: E402

# DeepSeek-V2 at 1 dense + 2 MoE layers, 4 of 16 routed experts held,
# top-6 (so a pick falls on a held expert a quarter of the time), 2 shared;
# tests/test_deepseek_v2.py serves the same size
DEEPSEEK_SMALL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "n_routed_experts": 4,
    "published": {"num_hidden_layers": 27, "n_routed_experts": 16},
    "vocab_size": 250, "serve": {"max_batch": 4, "max_len": 128}}
rehearsal.SMALL.setdefault("deepseek-v2-lite", DEEPSEEK_SMALL)
