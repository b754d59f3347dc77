import pytest

from bench import flops, spec

CFG = spec.load_json(spec.BENCH_DIR / "configs" / "chameleon-34b-l6.json")


def test_attention_pairs_count_valid_pairs_only():
    # two decoding rows at positions 9 and 0, one chunk of 3 from 5
    rec = flops.StepRecord("mixed", 0, 1, decode_pos=[9, 0], chunks=[(5, 3)])
    assert flops.attention_pairs(rec) == 10 + 1 + (6 + 7 + 8)


def test_step_flops_by_hand():
    rec = flops.StepRecord("decode", 0, 1, decode_pos=[4, 4, 4])
    d, ff, L, V = 8192, 22016, 6, 65536
    lin = 2 * L * (d * 8192 + 2 * d * 1024 + 8192 * d + 3 * d * ff)
    attn = 4 * 64 * 128 * L * 15
    assert flops.step_flops(CFG, rec) == pytest.approx(
        3 * lin + 3 * 2 * d * V + attn)


def test_padding_and_idle_rows_do_not_count():
    """A 3-token chunk counts 3 tokens whatever the step's lane width, and
    an idle slot counts nothing."""
    a = flops.StepRecord("mixed", 0, 1, decode_pos=[7], chunks=[(0, 3)], n_first=1)
    b = flops.StepRecord("mixed", 0, 1, decode_pos=[7], chunks=[(0, 3)], n_first=1)
    assert flops.step_flops(CFG, a) == flops.step_flops(CFG, b)
    one = flops.StepRecord("decode", 0, 1, decode_pos=[7])
    assert flops.step_flops(CFG, a) == pytest.approx(
        flops.step_flops(CFG, one) + 3 * flops.linear_flops_per_token(CFG)
        + flops.head_flops_per_row(CFG) + 4 * 64 * 128 * 6 * 6)

