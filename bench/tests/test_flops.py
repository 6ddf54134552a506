"""The GraphSAGE step's operation and byte counts against a hand count."""

from bench.flops import SageShapes, sage_grads_bytes, sage_grads_flops


def test_counts_match_hand_count():
    # B=2 seeds, fanout (3, 4), F=5 features, H=6 hidden, C=7 classes.
    s = SageShapes(batch=2, fanouts=(3, 4), feature_dim=5, hidden=6, classes=7)
    # Layer 1: 2 seeds + 6 first-hop rows = 8 rows, two 5x6 products:
    # forward 2*8*5*6*2 = 960, weight gradients as many.
    # Layer 2: 2 rows, two 6x7 products: forward 2*2*6*7*2 = 336,
    # weight gradients 336, gradients into layer 1's output 336.
    # Means: x_n2 2*3*4*5 = 120, x_n1 2*3*5 = 30, h_n1 2*3*6 = 36 and
    # its gradient 36.
    assert sage_grads_flops(s) == 960 * 2 + 336 * 3 + 120 + 30 + 36 * 2
    # Bytes: features 2*(1 + 3 + 12)*5 = 160 floats, 2 labels, weights
    # 2*5*6 + 6 + 2*6*7 + 7 = 157 read and 157 gradients written, 1 loss.
    assert sage_grads_bytes(s) == 4 * (160 + 2 + 2 * 157 + 1)


def test_products_cell_is_bound_by_bytes():
    from bench.peaks import peak

    s = SageShapes(batch=2000, fanouts=(10, 25), feature_dim=100, hidden=256, classes=47)
    pk = peak("TPU v5 lite")
    assert sage_grads_bytes(s) / pk.hbm_bytes_per_s > sage_grads_flops(s) / pk.flops_bf16
