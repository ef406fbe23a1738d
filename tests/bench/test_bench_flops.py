"""``step_mfu``'s FLOP count against a count by hand at Qwen2.5-32B
widths."""

from bench import registry


def test_flops_match_a_hand_count_at_qwen_widths():
    c = registry.config("qwen2.5-32b-l4")
    dense = registry.arch(c)
    # One layer: q 5120x5120, k and v 5120x1024 each, o 5120x5120, and the
    # gate, up and down projections 5120x27648 each.
    layer = 26_214_400 + 2 * 5_242_880 + 26_214_400 + 3 * 141_557_760
    assert dense.layer_matmul_params(c) == layer == 487_587_840
    head = 5120 * 152064
    assert dense.head_params(c) == head == 778_567_680
    per_token = 2 * (4 * layer + head)
    assert per_token == 5_457_838_080
    # A master tick of 16 busy rows x 8 slots is 0.699 TFLOP.
    tick = dense.window_flops(c, {"busy_tree_ticks": 16, "admissions": 0},
                              wave=8, max_len=512)
    assert tick == 128 * per_token
    assert abs(tick - 0.6987e12) < 1e9
    # A staged request is prefilled at the padded 512 through the layers
    # and through the head at its last position only.
    staged = dense.window_flops(c, {"busy_tree_ticks": 0, "admissions": 1},
                                wave=8, max_len=512)
    assert staged == 2 * (512 * 4 * layer + head)


def test_mfu_reader_uses_the_device_peak():
    import dataclasses

    from bench import registry as reg

    @dataclasses.dataclass
    class Ctx:
        flops: float
        window_s: float
        peak: dict

    read = reg.metric_reader("step_mfu")
    assert read(Ctx(197e12, 2.0, {"bf16_flops_per_s": 197e12})) == 50.0
    assert read(Ctx(1.0, 2.0, {})) is None
