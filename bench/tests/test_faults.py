"""The check sees the timed path broken: each fault a cell can have,
planted in the program underneath a whole small run on the CPU, makes
``correct`` come out false."""

import jax.numpy as jnp

from small import run_small


def test_sound_runs_are_correct():
    for w in ("tree_lstm.train", "tree_lstm.serve_poisson"):
        assert run_small(w)[0]["correct"] is True


def test_step_that_leaves_the_state_unchanged(monkeypatch):
    import repro.train.trainer as trainer_mod
    from repro.optim import OptState
    from repro.optim.adamw import global_norm

    def frozen(params, grads, state, *, lr, **_kw):
        return params, OptState(step=state.step + 1, mu=state.mu,
                                nu=state.nu), \
            {"grad_norm": global_norm(grads),
             "lr": jnp.asarray(lr, jnp.float32)}

    monkeypatch.setattr(trainer_mod, "adamw_update", frozen)
    result, _c, _o = run_small("tree_lstm.train")
    assert result["correct"] is False
    assert result["checks"]["grad_gap"]["value"] > 0.9


def test_half_the_batch_left_out(monkeypatch):
    from repro.pipeline import composer as comp_mod

    orig = comp_mod.BatchComposer.compose

    def halves(self, graphs, inputs=None, aux=None):
        batches, stats = orig(self, graphs, inputs, aux)
        for b in batches:
            k = max(1, len(b) // 2)
            b.graphs, b.sample_ids = b.graphs[:k], b.sample_ids[:k]
            b.inputs = b.inputs[:k] if b.inputs is not None else None
            b.aux = {n: v[:k] for n, v in b.aux.items()}
        return batches, stats

    monkeypatch.setattr(comp_mod.BatchComposer, "compose", halves)
    result, _c, _o = run_small("tree_lstm.train")
    assert result["correct"] is False
    assert result["checks"]["batch_shortfall"]["value"] > 0


def test_an_answer_altered(monkeypatch):
    import repro.serve.continuous as cont

    orig = cont.ContinuousBatchEngine._retire

    def altered(self, done):
        orig(self, done)
        for a in done:
            if a.req.request_id == 3 and a.req.root_state is not None:
                a.req.root_state = a.req.root_state.copy()
                a.req.root_state[0] += 0.05

    monkeypatch.setattr(cont.ContinuousBatchEngine, "_retire", altered)
    result, _c, _o = run_small("tree_lstm.serve_poisson")
    assert result["correct"] is False
    assert result["checks"]["root_rms_gap"]["value"] > 1e-4
