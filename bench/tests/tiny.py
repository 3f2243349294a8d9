"""A cell cut to a size a CPU test run holds: the real cell's files, with
the population, local steps and eval set made small."""
from __future__ import annotations

from bench import cells

TINY = dict(num_clients=6, clients_per_round=2, local_epochs=1, batch_size=8,
            samples_per_client=32, majority_per_client=22, eval_n_per_class=2)


# A static Dirichlet(0.5) label skew over 62 classes with ragged client
# sizes: the generator's and reference's other source, which no cell uses.
DIRICHLET = {"scenario": {"source": "dirichlet", "alpha": 0.5}}
DIRICHLET_CONFIG = {"num_classes": 62, "samples_min": 12}


def cell(name: str = "paper_cnn.case1b", strategies=None, rounds: int = 2,
         traffic=None, seeds_per_call: int = 1, **overrides) -> cells.Cell:
    real = cells.load_cell(name)
    cfg = dict(real.config, **TINY)
    cfg.update(overrides)
    n_strategies = len(strategies or real.traffic["strategies"])
    tr = dict(real.traffic, seeds_per_call=seeds_per_call, rounds_per_call=rounds,
              check_trials=n_strategies * seeds_per_call)
    tr.update(traffic or {})
    if strategies is not None:
        tr["strategies"] = list(strategies)
    return cells.Cell(name=name, chips=1, config=cfg, traffic=tr,
                      limits=real.limits, end_to_end=real.end_to_end,
                      per_layer=real.per_layer)
