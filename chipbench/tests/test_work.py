"""The work counts against hand counts on a small graph."""
from chipbench.work import saint

# 10 nodes, 24 directed edges, 16 features, hidden 8, 3 classes
R, E, F, H, C = 10, 24, 16, 8, 3


def test_saint_counts():
    ops, byt = saint.forward(R, E, F, H, C)
    hand_ops = (2 * 2 * 10 * 16 * 8 + 2 * 24 * 8
                + 2 * 2 * 10 * 8 * 8 + 2 * 24 * 8
                + 2 * 10 * 8 * 3)
    assert ops == hand_ops
    w = lambda k, n: k * n / 8 + n * 4          # noqa: E731
    adj = 24 * 4 + 11 * 4
    hand_bytes = (10 * 16 * 4 + 2 * w(16, 8) + adj + 10 * 8 * 4
                  + 10 * 8 * 4 + 2 * w(8, 8) + adj + 10 * 8 * 4
                  + 10 * 8 * 4 + w(8, 3) + 10 * 3 * 4)
    assert byt == hand_bytes


def test_flickr_counts():
    # Flickr at the configuration's hidden 256: the two 500 x 256
    # transforms are most of the ops, yet bytes bound the forward
    ops, byt = saint.forward(89250, 842900, 500, 256, 7)
    assert 2 * 2 * 89250 * 500 * 256 < ops < 75e9
    assert 500e6 < byt < 600e6
    assert byt / 819e9 > ops / 197e12
