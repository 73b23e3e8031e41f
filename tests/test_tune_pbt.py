"""PBT sweep over fake v4-16 TPU slices (own cluster: init/shutdown)."""
import os

import pytest

import ray_tpu as rt
from ray_tpu import tune
from ray_tpu.tune.search import grid_search


@pytest.mark.slow  # heavy battery; tier-1 budget (see CHANGES PR-13)
def test_pbt_improves_population(tmp_path):
    """PBT on fake v4-16 TPU slices: bad lr trials clone good ones and the
    whole population converges."""
    from ray_tpu.accel.tpu import TPU_POD_TYPE_LABEL, TPU_SLICE_NAME_LABEL, TPU_WORKER_ID_LABEL
    from ray_tpu.core.api import Cluster
    from ray_tpu.train import Checkpoint, RunConfig

    cluster = Cluster(initialize_head=False)
    tpu_nodes = [
        cluster.add_node(
            num_cpus=1,
            resources={"TPU": 4.0, f"TPU-v4-16-head": 1.0},
            labels={TPU_SLICE_NAME_LABEL: f"slice-{i}",
                    TPU_WORKER_ID_LABEL: "0",
                    TPU_POD_TYPE_LABEL: "v4-16"},
        )
        for i in range(4)
    ]
    rt.init(address=cluster.address)
    try:
        def trainable(config):
            import json
            import tempfile
            import time

            ckpt = tune.get_checkpoint()
            theta = 0.0
            if ckpt:
                with open(os.path.join(ckpt.path, "s.json")) as f:
                    theta = json.load(f)["theta"]
            for step in range(1, 17):
                time.sleep(0.25)  # pace steps so the controller sees
                                  # mid-run results (PBT acts on them)
                # Good lr -> fast approach to 10; lr near 0 -> crawl.
                theta = theta + config["lr"] * (10.0 - theta)
                d = tempfile.mkdtemp()
                with open(os.path.join(d, "s.json"), "w") as f:
                    json.dump({"theta": theta}, f)
                tune.report({"obj": theta, "training_iteration": step},
                            checkpoint=Checkpoint.from_directory(d))

        pbt = tune.PopulationBasedTraining(
            metric="obj", mode="max", perturbation_interval=4,
            hyperparam_mutations={"lr": tune.uniform(0.05, 0.9)},
            quantile_fraction=0.25, seed=0,
        )
        results = tune.Tuner(
            trainable,
            param_space={"lr": grid_search([0.001, 0.002, 0.5, 0.6])},
            tune_config=tune.TuneConfig(
                metric="obj", mode="max", scheduler=pbt,
                resources_per_trial={"TPU": 4.0},
            ),
            run_config=RunConfig(name="pbt", storage_path=str(tmp_path)),
        ).fit()
        assert not results.errors
        finals = sorted(r.metrics["obj"] for r in results)
        # Without PBT, lr=0.001 ends at ~0.16; with exploit/explore every
        # trial must end well above that.
        assert finals[0] > 2.0, finals
        assert results.get_best_result().metrics["obj"] > 9.0
    finally:
        rt.shutdown()
        cluster.shutdown()


