"""Operation and byte counts against hand counts."""
import json

import opcount
from harness import BENCH


def test_qmatmul_hand_counts():
    # decode: 32 rows x 576 -> 1536 with the 4 check columns
    w = opcount.qmatmul(32, 576, 1536)
    assert w["int8_ops"] == 2 * 32 * 576 * 1540 == 56_770_560
    assert w["bytes"] == 32 * 576 + 576 * 1540 + 4 * 32 * 1540 == 1_102_592
    # prefill bucket: 256 rows x 1536 -> 576, no check columns
    w = opcount.qmatmul(256, 1536, 576, checksum=False)
    assert w["int8_ops"] == 452_984_832
    assert w["bytes"] == 393_216 + 884_736 + 589_824


def test_qconv2d_hand_counts():
    # Table-1 conv_24x3x3x24 on one 194x194 tile, stride 1, check columns
    w = opcount.qconv2d(1, 194, 194, 24, 24, 3, 3, 1)
    assert w["int8_ops"] == 2 * 194 * 194 * 9 * 24 * 28 == 455_245_056
    assert w["bytes"] == 194 * 194 * 24 + 9 * 24 * 28 + 4 * 194 * 194 * 28
    # stem: 16 tiles of 388x388x3, stride 2 -> 194x194x24, no check
    w = opcount.qconv2d(16, 388, 388, 3, 24, 3, 3, 2, checksum=False)
    assert w["int8_ops"] == 2 * 16 * 194 * 194 * 9 * 3 * 24 == 780_420_096
    assert w["bytes"] == 16 * 388 * 388 * 3 + 9 * 3 * 24 + 4 * 16 * 194 * 194 * 24


def test_shipdet_macs_against_convspec():
    """The network as it runs (388 tile, 'SAME' strides) against the sum of
    the program's ConvSpec.macs: equal where the spec's input sizes are the
    ones the network sees, and 1.6% lower where the spec lists the Table-1
    sizes 98 and 50 for layers that see 97 and 49."""
    from repro.models.shipdet import network_specs
    cfg = json.loads((BENCH / "configs" / "shipdet-388-abft.json").read_text())
    spec = network_specs()
    assert sum(s.macs for s in spec) == 847_608_480
    ours = opcount.shipdet_macs_per_frame(cfg)
    assert ours == 834_384_096
    side = {"stem": 388, "conv_24x3x3x24": 194, "down1": 194,
            "conv_48x3x3x48": 97, "down2": 97, "conv_96x3x3x96": 49,
            "head1x1": 49, "det_head": 49}
    assert ours == sum(
        opcount.conv_out(side[s.name], s.stride) ** 2
        * s.kh * s.kw * s.cin * s.cout for s in spec)
    assert abs(sum(s.macs for s in spec) / ours - 1.0158) < 1e-3


def test_smollm_request_ops_is_sum_over_tokens():
    cfg = json.loads(
        (BENCH / "configs" / "smollm-135m-w8a8-bestmap.json").read_text())
    n_in, n_out = 37, 5
    want = {"bf16_ops": 0, "int8_ops": 0}
    for p in range(n_in + n_out - 1):
        t = opcount.smollm_token_ops(cfg, p, logits=p >= n_in - 1)
        for k in want:
            want[k] += t[k]
    assert opcount.smollm_request_ops(cfg, n_in, n_out) == want
    # one decode token at position 0 by hand: 30 layers of projections
    # (576x576 q, 2 x 576x192 k/v, 576x576 o) and attention over 1 position,
    # the int8 feed-forward (3 x 576x1536) and the 576x49152 head
    t = opcount.smollm_token_ops(cfg, 0, logits=True)
    assert t["bf16_ops"] == 30 * (2 * 576 * 960 + 2 * 576 * 576
                                  + 4 * 576) + 2 * 576 * 49152
    assert t["int8_ops"] == 30 * 6 * 576 * 1536
