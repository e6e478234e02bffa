"""The program's configuration must agree with the file's widths, expert and
latent widths among them: a mismatch stops the build before anything is
drawn; a file's null agrees with the program's 0; a key the file lacks is
not checked."""
import pytest

from bench import engine_build

ARCH = "deepseek-v2-236b"  # latent attention, 160 routed experts (top 6), 2 shared


def file_of(arch):
    from repro.configs import get_config

    mc = get_config(arch)
    return {"name": "f", "arch": arch, "num_hidden_layers": mc.n_layers,
            "hidden_size": mc.d_model, "n_routed_experts": mc.n_experts,
            "num_experts_per_tok": mc.top_k, "moe_intermediate_size": mc.moe_d_ff,
            "n_shared_experts": mc.n_shared_experts, "kv_lora_rank": mc.kv_lora_rank,
            "q_lora_rank": mc.q_lora_rank, "qk_nope_head_dim": mc.qk_nope_head_dim,
            "qk_rope_head_dim": mc.qk_rope_head_dim, "v_head_dim": mc.v_head_dim}


def test_matching_widths_pass():
    cfg = file_of(ARCH)
    assert engine_build.program_config(cfg).n_experts == 160
    dense = file_of("qwen3-32b")  # no experts, no latent: the file says null
    dense.update({k: None for k in ("n_routed_experts", "num_experts_per_tok",
                                    "moe_intermediate_size", "n_shared_experts",
                                    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                                    "qk_rope_head_dim", "v_head_dim")})
    engine_build.program_config(dense)


@pytest.mark.parametrize("key,value", [
    ("n_routed_experts", 64), ("num_experts", 64), ("num_experts_per_tok", 8),
    ("moe_intermediate_size", 1408), ("n_shared_experts", 1),
    ("n_shared_experts", None), ("kv_lora_rank", 256), ("q_lora_rank", None),
    ("qk_nope_head_dim", 64), ("qk_rope_head_dim", 32), ("v_head_dim", 64)])
def test_a_width_mismatch_stops_the_build(key, value):
    cfg = dict(file_of(ARCH), **{key: value})
    with pytest.raises(ValueError, match=key):
        engine_build.build(cfg, seed=1)
