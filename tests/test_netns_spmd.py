"""Genuinely-multi-address SPMD: two network namespaces, distinct IPs,
the real ssh-launcher path (r04 verdict item 7).

test_ssh_launcher.py runs localhost-as-remote — every worker still shares
the submitter's network identity, so the loopback-topology guard
(coordinator.py _cluster_info) and the WorkerConfig host plumbing had only
ever been exercised against registration *data*.  Here each worker runs in
its own network namespace with its own veth/IP on a bridge: worker-to-
coordinator traffic and the chief's jax.distributed coordination service
both cross real non-loopback links between distinct network identities —
the closest this single machine gets to two hosts.

Topology (root-only; skipped without ip-netns capability):

    root ns:  br-stpu 10.223.1.1/24
    stpu-nsb: eth0 10.223.1.2/24  (worker 0 — SPMD chief)
    stpu-nsc: eth0 10.223.1.3/24  (worker 1)

The fake ssh maps the host argument to ``ip netns exec`` — exactly the
launcher's pluggable exec-wrapper seam (submitter.py ssh_command).
"""

import json
import os
import stat
import subprocess

import pytest

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.coordinator.coordinator import JobSpec, JobState
from shifu_tensorflow_tpu.coordinator.submitter import JobSubmitter
from shifu_tensorflow_tpu.coordinator.worker import WorkerConfig
from shifu_tensorflow_tpu.data.reader import RecordSchema
from shifu_tensorflow_tpu.data.splitter import split_training_data

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "PYTHONPATH": REPO_ROOT,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}

BRIDGE = "br-stpu"
COORD_IP = "10.223.1.1"
NS = {"10.223.1.2": "stpu-nsb", "10.223.1.3": "stpu-nsc"}


def _ip(*args) -> subprocess.CompletedProcess:
    return subprocess.run(["ip", *args], capture_output=True, text=True)


def _netns_capable() -> bool:
    if os.geteuid() != 0:
        return False
    probe = _ip("netns", "add", "stpu-capability-probe")
    if probe.returncode != 0:
        return False
    _ip("netns", "del", "stpu-capability-probe")
    return True


pytestmark = pytest.mark.skipif(
    not _netns_capable(), reason="needs root + ip-netns capability"
)


@pytest.fixture
def netns_pair():
    """Two namespaces bridged to the root namespace; yields nothing, the
    module constants carry the addresses.  Teardown removes everything
    even when the test fails mid-run."""

    def teardown():
        for ns in NS.values():
            _ip("netns", "del", ns)
        _ip("link", "del", BRIDGE)

    teardown()  # sweep a previous crashed run's debris
    try:
        assert _ip("link", "add", BRIDGE, "type", "bridge").returncode == 0
        _ip("addr", "add", f"{COORD_IP}/24", "dev", BRIDGE)
        _ip("link", "set", BRIDGE, "up")
        for addr, ns in NS.items():
            veth = f"v-{ns[-3:]}-{os.getpid() % 1000}"[:15]
            assert _ip("netns", "add", ns).returncode == 0
            assert _ip("link", "add", veth, "type", "veth", "peer", "name",
                       "eth0", "netns", ns).returncode == 0
            _ip("link", "set", veth, "master", BRIDGE)
            _ip("link", "set", veth, "up")
            subprocess.run(["ip", "netns", "exec", ns, "ip", "addr", "add",
                            f"{addr}/24", "dev", "eth0"], check=True)
            subprocess.run(["ip", "netns", "exec", ns, "ip", "link", "set",
                            "eth0", "up"], check=True)
            subprocess.run(["ip", "netns", "exec", ns, "ip", "link", "set",
                            "lo", "up"], check=True)
        yield
    finally:
        teardown()


# fake ssh with REAL network isolation: the host argument selects the
# namespace the "remote" command runs in (loopback = the root namespace,
# for the guard test's deliberately-misconfigured chief)
NETNS_SSH = """#!/bin/sh
while [ "$1" = "-o" ]; do shift 2; done
host="$1"; shift
case "$host" in
%s
  127.0.0.1) exec /bin/sh -c "$*";;
  *) echo "netns-ssh: unknown host $host" >&2; exit 255;;
esac
exec ip netns exec "$ns" /bin/sh -c "$*"
""" % "\n".join(f'  {addr}) ns={ns};;' for addr, ns in NS.items())


@pytest.fixture
def netns_ssh(tmp_path):
    path = tmp_path / "netns-ssh"
    path.write_text(NETNS_SSH)
    path.chmod(path.stat().st_mode | stat.S_IEXEC)
    return str(path)


def _mc(epochs=2):
    return ModelConfig.from_json(
        {"train": {"numTrainEpochs": epochs, "validSetRate": 0.2,
                   "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.05, "Optimizer": "adam"}}}
    )


def _spec_and_cfg(psv_dataset, tmp_path, epochs=2):
    schema = RecordSchema(
        feature_columns=tuple(psv_dataset["feature_cols"]),
        target_column=psv_dataset["target_col"],
        weight_column=psv_dataset["weight_col"],
    )
    shards = split_training_data(psv_dataset["root"], 2)
    mc = _mc(epochs)

    def make_cfg(worker_id: str, addr) -> WorkerConfig:
        return WorkerConfig(
            worker_id=worker_id, coordinator_host=addr[0],
            coordinator_port=addr[1], model_config=mc, schema=schema,
            batch_size=32, checkpoint_dir=str(tmp_path / "ckpt"),
            heartbeat_interval_s=0.2, spmd=True,
        )

    spec = JobSpec(n_workers=2, shards=shards, spmd=True, epochs=epochs,
                   registration_timeout_s=120.0)
    return spec, make_cfg


def test_spmd_across_network_namespaces(psv_dataset, tmp_path, netns_ssh,
                                        netns_pair):
    """Two workers with DISTINCT network identities train one model: the
    chief's jax.distributed service binds in one namespace and the peer
    dials it across the bridge; the coordinator is reached at a third
    address.  No loopback shortcut exists on any leg."""
    spec, make_cfg = _spec_and_cfg(psv_dataset, tmp_path)
    submitter = JobSubmitter(
        spec, make_cfg, launcher="ssh",
        hosts=list(NS),  # 10.223.1.2 (chief), 10.223.1.3
        ssh_command=[netns_ssh],
        worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"),
        bind_host="0.0.0.0",
        advertise_host=COORD_IP,
    )
    result = submitter.run(timeout_s=300.0)
    assert result.state == JobState.FINISHED, result.failure_reason
    recs = {r.worker_index: r for r in submitter.coordinator.workers.values()}
    # every worker registered ITS OWN namespace address — the plumbing the
    # localhost-as-remote test could not distinguish from defaults
    assert recs[0].host == "10.223.1.2"
    assert recs[1].host == "10.223.1.3"
    assert len(result.epoch_summaries) == 2


def test_loopback_chief_guard_fires_against_real_network(
    psv_dataset, tmp_path, netns_ssh, netns_pair
):
    """The _cluster_info loopback guard, against reality: the hosts list
    itself assigns the chief to 127.0.0.1 (so the launcher's own
    loopback-healing cannot fix it) while the peer runs in a namespace and
    registers its routable address.  Without the guard the peer would dial
    ITS OWN loopback for the jax coordination service and hang to the
    barrier timeout; with it the job fails fast with an actionable
    reason."""
    spec, make_cfg = _spec_and_cfg(psv_dataset, tmp_path)

    submitter = JobSubmitter(
        spec, make_cfg, launcher="ssh",
        hosts=["127.0.0.1", "10.223.1.3"],  # chief deliberately loopback
        ssh_command=[netns_ssh],
        worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"),
        bind_host="0.0.0.0",
        advertise_host=COORD_IP,
    )
    result = submitter.run(timeout_s=180.0)
    assert result.state == JobState.FAILED
    assert "loopback" in (result.failure_reason or "")


def test_netns_worker_logs_carry_distinct_identities(
    psv_dataset, tmp_path, netns_ssh, netns_pair
):
    """The per-worker log files (container-log parity) must show each
    worker launched through its own namespace — a regression here would
    mean the exec wrapper silently collapsed back to one host."""
    spec, make_cfg = _spec_and_cfg(psv_dataset, tmp_path)
    marker = tmp_path / "host-markers"
    marker.mkdir()
    # wrap the wrapper: record which namespace each launch entered
    logging_ssh = tmp_path / "logging-netns-ssh"
    logging_ssh.write_text(
        "#!/bin/sh\n"
        f'echo "$1" >> {marker}/hosts.log\n'
        + NETNS_SSH.split("\n", 1)[1]
    )
    logging_ssh.chmod(logging_ssh.stat().st_mode | stat.S_IEXEC)
    submitter = JobSubmitter(
        spec, make_cfg, launcher="ssh", hosts=list(NS),
        ssh_command=[str(logging_ssh)], worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"), bind_host="0.0.0.0",
        advertise_host=COORD_IP,
    )
    result = submitter.run(timeout_s=300.0)
    assert result.state == JobState.FINISHED, result.failure_reason
    launched = set((marker / "hosts.log").read_text().split())
    assert launched == set(NS)
