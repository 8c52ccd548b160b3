"""Multi-host bootstrap env resolution (init_process_group analog)."""

import jax

from apex_tpu.parallel.launch import distributed_env, init_distributed


class TestDistributedEnv:
    def test_jax_native_vars(self):
        env = {"COORDINATOR_ADDRESS": "10.0.0.1:1234",
               "PROCESS_ID": "3", "NUM_PROCESSES": "16"}
        assert distributed_env(env) == ("10.0.0.1:1234", 3, 16)

    def test_torch_style_vars(self):
        env = {"MASTER_ADDR": "host0", "MASTER_PORT": "29500",
               "RANK": "2", "WORLD_SIZE": "8"}
        assert distributed_env(env) == ("host0:29500", 2, 8)

    def test_torch_default_port_and_node_rank(self):
        env = {"MASTER_ADDR": "host0", "NODE_RANK": "1",
               "WORLD_SIZE": "4"}
        coord, pid, nproc = distributed_env(env)
        assert coord == "host0:8476" and pid == 1 and nproc == 4

    def test_rank_beats_node_rank(self):
        # torchrun, 2 nodes x 4 procs: only the global RANK is unique
        env = {"MASTER_ADDR": "host0", "RANK": "5", "NODE_RANK": "1",
               "WORLD_SIZE": "8"}
        assert distributed_env(env)[1] == 5

    def test_empty(self):
        assert distributed_env({}) == (None, None, None)

    def test_native_wins_over_torch(self):
        env = {"COORDINATOR_ADDRESS": "c:1", "MASTER_ADDR": "m",
               "PROCESS_ID": "0", "RANK": "9", "NUM_PROCESSES": "2",
               "WORLD_SIZE": "99"}
        assert distributed_env(env) == ("c:1", 0, 2)


class TestInitDistributed:
    def test_single_host_noop(self, monkeypatch):
        for var in ("COORDINATOR_ADDRESS", "MASTER_ADDR", "RANK",
                    "WORLD_SIZE", "PROCESS_ID", "NUM_PROCESSES"):
            monkeypatch.delenv(var, raising=False)
        import apex_tpu.parallel.launch as launch
        monkeypatch.setattr(launch, "_initialized", False)
        assert init_distributed() == 1
        # idempotent
        assert init_distributed() == jax.process_count()

    def test_world_size_one_noop(self, monkeypatch):
        import apex_tpu.parallel.launch as launch
        monkeypatch.setattr(launch, "_initialized", False)
        monkeypatch.setenv("MASTER_ADDR", "localhost")
        monkeypatch.setenv("WORLD_SIZE", "1")
        monkeypatch.setenv("RANK", "0")
        assert init_distributed() == 1

    def test_latched_initialized_short_circuits(self, monkeypatch):
        import apex_tpu.parallel.launch as launch
        monkeypatch.setattr(launch, "_initialized", True)

        def boom(*a, **k):
            raise AssertionError("must not re-initialize")

        monkeypatch.setattr(jax.distributed, "initialize", boom)
        assert init_distributed("10.0.0.1:1", 8, 0) == jax.process_count()

    def test_world_size_without_coordinator_raises(self, monkeypatch):
        import pytest

        import apex_tpu.parallel.launch as launch
        monkeypatch.setattr(launch, "_initialized", False)
        for var in ("COORDINATOR_ADDRESS", "MASTER_ADDR"):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("WORLD_SIZE", "8")
        monkeypatch.setenv("RANK", "2")
        with pytest.raises(RuntimeError, match="no coordinator"):
            init_distributed()

    def test_master_addr_without_ranks_raises(self, monkeypatch):
        import pytest

        import apex_tpu.parallel.launch as launch
        monkeypatch.setattr(launch, "_initialized", False)
        for var in ("COORDINATOR_ADDRESS", "WORLD_SIZE", "RANK",
                    "NODE_RANK", "PROCESS_ID", "NUM_PROCESSES",
                    *launch._CLUSTER_ENV_MARKERS):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("MASTER_ADDR", "host0")
        with pytest.raises(RuntimeError, match="WORLD_SIZE"):
            init_distributed()

    def test_master_addr_under_cluster_warns_and_defers(self, monkeypatch):
        import warnings

        import apex_tpu.parallel.launch as launch
        monkeypatch.setattr(launch, "_initialized", False)
        for var in ("COORDINATOR_ADDRESS", "WORLD_SIZE", "RANK",
                    "NODE_RANK", "PROCESS_ID", "NUM_PROCESSES",
                    *launch._CLUSTER_ENV_MARKERS):
            monkeypatch.delenv(var, raising=False)
        # Slurm host where a site profile incidentally exports MASTER_ADDR:
        # must NOT abort, and must NOT pass the untrustworthy coordinator
        # through (it is often localhost — every node would connect to
        # itself); jax's cluster plugin autodetects all three fields.
        monkeypatch.setenv("MASTER_ADDR", "host0")
        monkeypatch.setenv("SLURM_JOB_ID", "1234")
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: calls.append(kw))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            init_distributed()
        assert any("managed-cluster" in str(w.message) for w in caught)
        assert calls == [{"coordinator_address": None,
                          "num_processes": None, "process_id": None}]
