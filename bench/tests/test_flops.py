"""Operation and byte counts checked by hand for one decode-only step and one
step with a prefill chunk (Qwen3-32B widths, 8 layers), and handed to the
configuration's reference module where it states its own."""
import json
import sys
import types

from bench import flops
from bench.tests.tiny import ROOT

CFG = json.loads((ROOT / "bench" / "configs" / "qwen3-32b-l8.json").read_text())


def test_layer_weights():
    # q 5120x8192, k and v 5120x1024 each, o 8192x5120, FFN 3 x 5120x25600
    assert flops.layer_matmul_params(CFG) == (
        5120 * 8192 + 2 * 5120 * 1024 + 8192 * 5120 + 3 * 5120 * 25600)


def test_decode_only_step():
    # two decode rows attending 100 and 17 keys (their new token included)
    q, kv = (1, 1), (100, 17)
    per_layer = 4 * 64 * 128 * (100 + 17)
    assert flops.attention_flops(CFG, q, kv) == per_layer
    # pages: ceil(100/16)=7 and ceil(17/16)=2; K and V, 8 heads, 128 wide,
    # bf16; plus each row's 64x128 query read and output written
    assert flops.attention_bytes(CFG, q, kv, 16) == 2 * (2 * 9 * 16 * 8 * 128 + 2 * 2 * 64 * 128)
    w = flops.layer_matmul_params(CFG)
    assert flops.step_flops(CFG, q, kv, sampled=2) == (
        8 * (2 * w * 2 + per_layer) + 2 * 5120 * 151936 * 2)


def test_chunk_step():
    # one decode (kv 40) and a 256-token chunk at positions 512..767
    q, kv = (1, 256), (40, 768)
    keys = 40 + sum(range(513, 769))  # the chunk's j-th row sees 513 + j keys
    assert flops.segment_keys(256, 768) == sum(range(513, 769))
    assert flops.attention_flops(CFG, q, kv) == 4 * 64 * 128 * keys
    pages = 3 + 48
    assert flops.attention_bytes(CFG, q, kv, 16) == 2 * (
        2 * pages * 16 * 8 * 128 + 2 * 257 * 64 * 128)
    # the head runs for the sampled rows only: the decode row, not the chunk
    w = flops.layer_matmul_params(CFG)
    assert flops.step_flops(CFG, q, kv, sampled=1) == (
        8 * (2 * w * 257 + 4 * 64 * 128 * keys) + 2 * 5120 * 151936)


def test_dense_gqa_states_no_counts_of_its_own():
    from bench.references import dense_gqa

    assert not any(hasattr(dense_gqa, n) for n in (
        "layer_matmul_params", "attention_flops", "attention_bytes"))
    # the counts of a mixed step as they were before counts could be handed
    # to the reference module
    q, kv = (1, 1, 1, 128), (7000, 300, 12000, 4096)
    assert flops.layer_matmul_params(CFG) == 487587840
    assert flops.attention_flops(CFG, q, kv) == 17545953280.0
    assert flops.attention_bytes(CFG, q, kv, 16) == 100171776.0
    assert flops.step_flops(CFG, q, kv, sampled=3) == 1167019212800.0


def test_reference_module_states_its_own_counts(monkeypatch):
    stub = types.ModuleType("bench.references.stub_moe")
    stub.layer_matmul_params = lambda cfg: 1000  # e.g. active experts only
    monkeypatch.setitem(sys.modules, stub.__name__, stub)
    cfg = dict(CFG, reference="stub_moe")
    q, kv = (1, 256), (40, 768)
    w = flops.layer_matmul_params(CFG)
    assert flops.layer_matmul_params(cfg) == 1000
    assert flops.step_flops(cfg, q, kv, 1) == (
        flops.step_flops(CFG, q, kv, 1) - 8 * 2 * (w - 1000) * 257)
    # what the module does not state is the dense count
    assert flops.attention_flops(cfg, q, kv) == flops.attention_flops(CFG, q, kv)
    stub.attention_flops = lambda cfg, q_lens, kv_lens: 5.0 * sum(kv_lens)
    stub.attention_bytes = lambda cfg, q_lens, kv_lens, page: 3.0 * page
    assert flops.attention_flops(cfg, q, kv) == 5.0 * 808
    assert flops.attention_bytes(cfg, q, kv, 16) == 48.0
    assert flops.step_flops(cfg, q, kv, 1) == (
        8 * (2 * 1000 * 257 + 5.0 * 808) + 2 * 5120 * 151936)
