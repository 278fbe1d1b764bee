"""Operation and byte counts against numbers worked by hand."""

import costs
import generate

H, X = 512, 256


def test_level_counts_of_a_small_tree():
    # leaves 0, 1, 2; 3 = (0, 1); 4 = (3, 2)
    tree = [[], [], [], [0, 1], [3, 2]]
    assert costs.level_counts([tree]) == [(3, 0, 0), (1, 2, 1), (1, 2, 1)]
    # Two structures side by side share their levels.
    assert costs.level_counts([tree, generate.chain(2)]) == [
        (4, 0, 0), (2, 3, 2), (1, 2, 1)]


def test_treelstm_forward_kernel():
    # One level of two vertices, one with two children, one a leaf:
    # 2 forget products (2·512·512 each) + 3 products of the sum.
    flops, nbytes = costs.fwd_kernel("treelstm", H, n_v=2, n_e=2, n_i=1)
    assert flops == 2 * 2 * 512 * 512 + 3 * 2 * 512 * 512 == 2_621_440
    weights = 4 * (4 * 512 * 512 + 4 * 512)            # ui uf uo uu, b
    rows = 4 * (2 * 1024 + 2 * (2048 + 1024))          # children; gates+state
    assert nbytes == weights + rows == 4_235_264


def test_lstm_kernels():
    flops, nbytes = costs.fwd_kernel("lstm", H, n_v=64, n_e=64, n_i=64)
    assert flops == 64 * 2 * 512 * 2048
    assert nbytes == 4 * (512 * 2048 + 2048) + 4 * (64 * 1024
                                                     + 64 * (2048 + 1024))
    bflops, bbytes = costs.bwd_kernel("lstm", H, n_v=64, n_e=64, n_i=64)
    assert bflops == flops
    assert bbytes == 4 * (512 * 2048 + 2048) + 4 * (64 * (1024 + 2048)
                                                    + 64 * 3 * 1024)


def test_train_flops_per_vertex():
    # A chain of 3: 3 projections, 2 recurrent edges.
    counts = costs.level_counts([generate.chain(3)])
    proj = 2 * 256 * 2048
    rec = 2 * 512 * 2048
    assert costs.train_flops("lstm", X, H, counts) == 3 * 2 * proj + 2 * 3 * rec
    # The paper's per-vertex forward figures: 3.1 MFLOP for an LSTM
    # vertex with a predecessor, 3.67 for a Tree-LSTM vertex with two
    # children.
    assert proj + rec == 3_145_728
    assert proj + costs.recurrent_flops("treelstm", H, 2, 1) == 3_670_016


def test_least_time_names_its_bound():
    peaks = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = costs.least_seconds(197e12, 1.0, peaks)
    assert (t, bound) == (1.0, "compute")
    t, bound = costs.least_seconds(1.0, 819e9, peaks)
    assert (t, bound) == (1.0, "memory")
