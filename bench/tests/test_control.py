"""The control — the plain reference in bfloat16, put in the program's
place — fails the paper cell's check.  At the paper's width and local
training (4 epochs of 10 batches of 32), on two clients a round, one round:
bfloat16 weights and Adam moments shift every trial's loss the same way,
which is what ``loss_mean_gap`` reads."""
from __future__ import annotations

from bench import cells, correct, traffic_gen
from bench.references import cnn as ref

SEED = 2 ** 31 + 4243


def test_the_control_is_refused():
    real = cells.load_cell("paper_cnn.case1b")
    cfg = dict(real.config, num_clients=8, clients_per_round=2,
               eval_n_per_class=20)
    tr = dict(real.traffic, rounds_per_call=1, seeds_per_call=1)
    plan = traffic_gen.call_plans(cfg, tr, SEED, 0)[0]
    seed = int(traffic_gen.call_seeds(tr, SEED, 0)[0])
    trials = []
    for strat in tr["strategies"]:
        want = ref.run_trial(cfg, tr, plan, strat, seed)
        ctl = ref.run_trial(cfg, tr, plan, strat, seed,
                            precision=ref.CONTROL_PRECISION, ties=False)[0]
        trials.append(correct.trial_gaps(ctl, want))
    numbers = correct.aggregate(trials)
    ok, _, _ = correct.judge(numbers, real.limits)
    assert not ok, numbers
    assert numbers["num_selected_gap"] == 0     # selection is not precision's
