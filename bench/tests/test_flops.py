"""Operation and byte counts checked by hand for one decode-only step and one
step with a prefill chunk (Qwen3-32B widths, 8 layers)."""
import json

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
