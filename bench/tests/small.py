"""Small stand-ins for the cells' configurations and traffic, so that a
whole run fits a CPU test."""

SMALL = {
    "tree_lstm.train": (
        {"vertex_args": {"input_dim": 8, "hidden": 16, "arity": 2}},
        {"corpus_size": 200, "batch": 16}),
    "var_lstm.train": (
        {"vertex_args": {"input_dim": 8, "hidden": 16}},
        {"corpus_size": 300, "batch": 16}),
    "tree_lstm.serve_poisson": (
        {"vertex_args": {"input_dim": 8, "hidden": 16, "arity": 2}},
        {"rate_per_s": 20.0,
         "structure": {"shape": "random_binary_tree", "mu": 1.8,
                       "sigma": 0.5, "min": 2, "max": 12},
         "engine": {"num_rows": 512, "frontier_width": 32}}),
}


def run_small(workload, seed=3_000_000_007, seconds=1.0, trace=False):
    import run
    cfg, traffic = SMALL[workload]
    return run.run_cell(workload, seed, seconds, trace, cfg_override=cfg,
                        traffic_override=traffic, require_tpu=False)
