"""The kernels' and the dense family's operation and byte counts against
hand-worked shapes."""
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from lib import spec


def test_paged_decode_cost_by_hand():
    k = spec.kernel_cost("paged_decode")
    # chatglm3-6b widths; a slot at 1025 cached positions (65 pages of
    # 16) and one at 16 (one page)
    fl, by = k.cost(32, 2, 128, [1025, 16], 16)
    assert fl == 4 * 32 * 128 * (1025 + 16) == 17_055_744
    kv = 2 * 2 * 128 * 2              # k and v, 2 heads, 128 dims, bf16
    qo = 2 * 32 * 128 * 2             # q read and output written
    assert by == (65 * 16 + 16) * kv + 2 * qo == 1_114_112


def test_flash_prefill_cost_by_hand():
    k = spec.kernel_cost("flash_prefill")
    fl, by = k.cost(1, 1024, 32, 2, 128)
    assert fl == 4 * 32 * 128 * (1024 * 1025 // 2) == 8_598_323_200
    assert by == 2 * 1024 * 128 * (2 * 32 + 2 * 2) == 17_825_792
    fl2, _ = k.cost(2, 1024, 32, 2, 128, causal=False)
    assert fl2 == 2 * 4 * 32 * 128 * 1024 * 1024


def test_model_flops_by_hand():
    flops = spec.reference("dense_gqa")
    m = dict(d_model=8, num_heads=2, num_kv_heads=1, head_dim=4, d_ff=16,
             vocab_size=10, num_layers=3)
    # q and o 8x8 each, k and v 8x4 each, MLP 3 x 8x16
    assert flops.layer_params(m) == 64 + 64 + 32 + 32 + 384 == 576
    assert flops.decode_flops(m, 5) == 3 * (2 * 576 + 4 * 2 * 4 * 5) \
        + 2 * 8 * 10
    assert flops.prefill_flops(m, 4) == 3 * (2 * 576 * 4 + 4 * 2 * 4 * 10) \
        + 2 * 8 * 10


@pytest.mark.parametrize("name,params,decode,prefill", [
    ("mistral-7b-v0.3", 218_103_808, 14_772_338_688.0,
     14_569_065_938_944.0),
    ("chatglm3-6b", 203_948_032, 12_430_868_480.0, 11_936_481_673_216.0)])
def test_model_flops_at_published_widths(name, params, decode, prefill):
    """The counts ``step_mfu`` reads at the cells' own widths: a token
    decoded over 1,040 positions and a 1,024-token prefill."""
    flops = spec.reference("dense_gqa")
    m = spec.config(name)["model"]
    assert flops.layer_params(m) == params
    assert flops.decode_flops(m, 1040) == decode
    assert flops.prefill_flops(m, 1024) == prefill
