"""``correct`` on the CPU at a tiny size, with the timed path broken
underneath: each fault a training cell can have makes it false."""
import jax
import pytest

from chip.test_chip_bench_check import run, tiny_cell


def broken_step(kind):
    """A ``build_train_step`` whose step carries one fault."""
    from repro.train import step as step_mod
    build = step_mod.build_train_step

    def build_broken(model, tcfg, n_nodes, *, phase, **kw):
        if kind == "no_exchange" or (kind == "no_gossip"
                                     and phase == "gossip"):
            return build(model, tcfg, n_nodes, phase="none", **kw)
        inner = build(model, tcfg, n_nodes, phase=phase, **kw)
        if kind == "no_gossip":
            return inner

        def step(state, batch, lr):
            if kind == "half_batch":
                batch = jax.tree.map(lambda x: x[:, :x.shape[1] // 2], batch)
                return inner(state, batch, lr)
            new, metrics = inner(state, batch, lr)
            return state, metrics              # "frozen"

        return step

    return build_broken


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "no_exchange",
                                   "no_gossip"])
def test_a_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    from repro.train import trainer
    monkeypatch.setattr(trainer, "build_train_step", broken_step(fault))
    cell, tb = tiny_cell("tiny-lm-1chip", "lm100m-pga-1chip")
    r = run(cell, tb)
    assert not r["correct"], r["checks"]


def test_an_input_token_altered_where_it_is_made_is_not_correct(
        monkeypatch):
    from repro.data import synthetic
    make = synthetic.SyntheticStream.get_batch

    def altered(self, step):
        batch = make(self, step)
        batch["inputs"][0, 0, 0] = (batch["inputs"][0, 0, 0] + 1) % 7
        return batch

    monkeypatch.setattr(synthetic.SyntheticStream, "get_batch", altered)
    cell, tb = tiny_cell("tiny-lm-1chip", "lm100m-pga-1chip")
    r = run(cell, tb)
    assert not r["correct"] and r["checks"]["input_gap"]["value"] > 0
