import jax
import jax.numpy as jnp
import pytest

from bench import work

PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
GRANITE = {"model_type": "llama", "hidden_size": 4096, "num_hidden_layers": 8,
           "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
           "intermediate_size": 12800, "vocab_size": 49155}


@pytest.mark.parametrize("m,k,n", [(1, 4096, 4096), (32, 4096, 12800), (64, 12800, 4096)])
def test_ops_are_the_product_whatever_the_tier(m, k, n):
    # 64 plane passes at 8 bits or 16 at 4: the work charged is the product's
    assert work.linear_ops(m, k, n) == 2 * m * k * n
    for bits in (8, 6, 4):
        assert work.least_seconds(m, k, n, bits, PEAKS) >= work.linear_ops(m, k, n) / 393e12


@pytest.mark.parametrize("m,k,n", [(32, 4096, 1024), (7, 4096, 49155)])
def test_bytes_differ_by_tier_only_through_the_packed_prefix(m, k, n):
    b8, b4 = work.linear_bytes(m, k, n, 8), work.linear_bytes(m, k, n, 4)
    assert b8 - b4 == 4 * work.BOOTH_BITS_PER_PLANE * k * n // 8
    assert b4 - work.linear_bytes(m, k, n, 0) == 4 * work.BOOTH_BITS_PER_PLANE * k * n // 8


def test_decode_at_m32_is_bandwidth_bound_and_prefill_at_m64_too():
    for m in (32, 64):
        t = work.least_seconds(m, 4096, 12800, 8, PEAKS)
        assert t == work.linear_bytes(m, 4096, 12800, 8) / 819e9


def test_prefix_bytes_match_the_programs_packed_planes():
    from repro.core.precision import PrecisionPolicy
    from repro.models.quant import quantize_params

    k, n = 512, 256
    pol = PrecisionPolicy.uniform(8, 8, variant="booth", level="bitplane")
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n)).astype(jnp.bfloat16)
    packed = quantize_params({"proj": {"w": w}}, pol, plane_cache=True)["proj"]["w_planes"].packed
    for bits in (8, 4):
        stored = packed.mag[:bits].nbytes + packed.sign[:bits].nbytes
        assert stored == work.linear_bytes(0, k, n, bits) - 4 * n


def test_linears_of_one_token():
    lin = work.linears(GRANITE)
    assert len(lin) == 7 * 8 + 1
    assert sum(k * n for _, k, n in lin[:7]) == 4096 * (4096 + 2 * 1024 + 4096 + 3 * 12800)
    assert lin[-1] == ("lm_head", 4096, 49155)


def test_prompt_and_decode_ops_agree():
    blocks_head = work.decode_ops(GRANITE, 0)
    head = 2 * 4096 * 49155
    attn = 4 * 8 * 32 * 128
    # a prompt of one token is one decode of position 0
    assert work.prompt_ops(GRANITE, 1) == blocks_head
    # a prompt is its positions' decodes without their lm_heads but the last
    s = 37
    want = sum(work.decode_ops(GRANITE, p) for p in range(s)) - (s - 1) * head
    assert work.prompt_ops(GRANITE, s) == want
    assert work.decode_ops(GRANITE, 9) - work.decode_ops(GRANITE, 8) == attn
