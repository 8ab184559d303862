"""``correct`` at a tiny size: the system passes, the control and each
fault the cells can have fail.  The control is the reference in a lower
precision (served) or with a broken guarantee (placement); the faults are
planted in the timed path after set-up."""
import jax
import pytest

import bench_testkit as kit
import control
import harness

SEEDS = [3, 2**33 + 1, 77]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return kit.make_root(tmp_path_factory.mktemp("bench"))


def _sut(tiny, cell, seed):
    root, bench = tiny
    _, cfg, mix = harness.find_cell(bench, cell, root)
    driver = harness.load_module(root / "bench" / "drivers" / f"{cfg['driver']}.py")
    sut = driver.Cell(cfg, mix, seed, jax.devices()[:1], root)
    rec = sut.window(2.0, jax.profiler.TraceAnnotation, harness.Tracer(False))
    return sut, sut.check(rec)


@pytest.mark.parametrize("seed", SEEDS)
def test_served_float8_control_fails_where_the_system_passes(tiny, seed):
    sut, checks = _sut(tiny, kit.SERVED, seed)
    gap, limit = checks["logit_gap"]
    assert gap <= limit
    assert control.served_control(sut)["control_logit_gap"] > limit


@pytest.mark.parametrize("seed", SEEDS)
def test_placement_control_fails_where_the_system_passes(tiny, seed):
    sut, checks = _sut(tiny, kit.PLACED, seed)
    assert checks["answers_wrong"] == (0, 0)
    assert control.placement_control(sut)["control_answers_wrong"] > 0


def _alter_tokens(sut):
    vocab = sut.shape.vocab
    for e in sut.engines:
        decode = e._decode

        def broken(params, cache, tokens, lengths, decode=decode):
            nxt, cache = decode(params, cache, tokens, lengths)
            return (nxt + 1) % vocab, cache

        e._decode = broken


def _alter_answers(sut):
    state, deploy = sut.state, sut.engine.deploy

    def broken(st, ws):
        res = deploy(st, ws)
        placed = [w for w in ws if w not in res.pending]
        if placed:
            gid, pl = state.placement_of(placed[0].wid)
            state.remove(pl.wid, gid)
            for other in sorted(state.gpus, reverse=True):
                g = state.gpus[other]
                prof = g.device.profile(pl.profile_id)
                idx = g.first_feasible_index(prof)
                if other != gid and idx is not None:
                    state.place(pl.wid, other, idx)
                    break
        return res

    sut.engine.deploy = broken


@pytest.mark.parametrize("cell,fault", [(kit.SERVED, _alter_tokens),
                                        (kit.PLACED, _alter_answers)])
def test_an_altered_answer_is_not_correct(tiny, cell, fault):
    root, bench = tiny
    res = kit.run(root, bench, cell, seed=5, hook=fault)
    assert res["correct"] is False


def test_fleet_generator_copy_matches_the_papers_generator():
    from repro.core.simulator import generate_test_case

    cfg = kit._json(kit.ROOT / "bench" / "configs" / "fleet-a100-1024.json")
    ref = harness.load_module(kit.ROOT / "bench" / "configs" / "fleet-a100-1024.py")
    fleet, wids = ref.initial_fleet(cfg)
    case = generate_test_case(cfg["fleet_seed"], n_gpus=cfg["n_gpus"])
    got = {gid: tuple(sorted((p.wid, p.profile_id, p.index) for p in g.placements))
           for gid, g in case.initial.gpus.items() if g.placements}
    assert fleet.layout() == got
    assert len(got) == 614 and len(wids) == 1518
