"""The paper's six non-IID cases (§III) under vanilla FedAvg — reproduces the
Table-I structure: A-cases train partially, B-cases collapse to ~chance,
IID converges.

The experiment is declared as data (repro.fl.experiment): seven case
scenarios × 1 strategy × 1 seed, dispatched to the compiled simulation
engine — the scenario axis is vmapped, the round loop is a device-resident
lax.scan, no per-case re-jits.  Swap ``engine="host"`` to call the same
round step once per round from Python, or add strategies/seeds/transforms
without touching any engine code.

    PYTHONPATH=src python examples/six_noniid_cases.py
"""
from repro.configs.paper_cnn import FLConfig
from repro.core import CASES
from repro.fl import ExperimentSpec, ScenarioSpec, run


def main():
    cfg = FLConfig(num_clients=16, clients_per_round=6, global_epochs=5,
                   local_epochs=2, batch_size=16)
    spec = ExperimentSpec(
        scenarios=tuple(ScenarioSpec.from_case(c, samples_per_client=48)
                        for c in CASES),
        strategies=("random",), seeds=(0,), engine="sim", fl=cfg)
    res = run(spec)
    print(f"# compiled grid: {len(CASES)} cases × 1 strategy × 1 seed, "
          f"compile {res.compile_s:.1f}s + run {res.wall_s:.1f}s")
    print(f"{'case':10s} {'final_acc':>9s} {'final_loss':>10s}")
    for case in CASES:
        traj = res.trajectory(case, "random", 0)
        print(f"{case:10s} {traj['accuracy'][-1]:9.4f} "
              f"{traj['loss'][-1]:10.4f}")


if __name__ == "__main__":
    main()
