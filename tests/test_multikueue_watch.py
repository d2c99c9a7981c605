"""MultiKueue watch streams (verdict r3 item 6): worker-side events are
PUSHED to the manager over a long-poll watch with resume tokens, not
polled one GET per assigned workload per reconcile; a reconnect replays
every missed event.  Reference: multikueuecluster.go:187-226.

The worker here is an in-process Driver behind a real WorkerServer HTTP
boundary, so the transport (sockets, long-poll, reconnect) is real while
staying fast enough for the suite.
"""

from __future__ import annotations

import time

from kueue_tpu.api.types import (
    AdmissionCheck,
    AdmissionCheckState,
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    MultiKueueConfig,
    PodSet,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_tpu.admissionchecks.multikueue import (
    MultiKueueController,
    WorkerCluster,
)
from kueue_tpu.controller.driver import Driver
from kueue_tpu.remote import HttpWorkerClient, WorkerServer


def free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def make_worker():
    d = Driver()
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    d.apply_cluster_queue(ClusterQueue(
        name="cq", resource_groups=[ResourceGroup(
            covered_resources=["cpu"], flavors=[
                FlavorQuotas(name="default", resources={
                    "cpu": ResourceQuota(nominal=8000)})])]))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    return d


def make_manager():
    d = Driver()
    d.apply_resource_flavor(ResourceFlavor(name="default"))
    d.apply_admission_check(AdmissionCheck(
        name="mk", controller_name="kueue.x-k8s.io/multikueue"))
    d.apply_cluster_queue(ClusterQueue(
        name="cq", admission_checks=["mk"],
        resource_groups=[ResourceGroup(
            covered_resources=["cpu"], flavors=[
                FlavorQuotas(name="default", resources={
                    "cpu": ResourceQuota(nominal=8000)})])]))
    d.apply_local_queue(LocalQueue(name="lq", cluster_queue="cq"))
    return d


class CountingClient(HttpWorkerClient):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.get_calls = 0

    def get_workload(self, key):
        self.get_calls += 1
        return super().get_workload(key)


def wait_for(cond, timeout=10.0, step=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return False


def setup_watch_pair():
    worker = make_worker()
    port = free_port()
    server = WorkerServer(worker, port=port)
    server.start()
    manager = make_manager()
    client = CountingClient(f"http://127.0.0.1:{port}", timeout=2.0)
    cluster = WorkerCluster(name="w1", client=client)
    ctl = MultiKueueController(
        manager, "mk", MultiKueueConfig(name="cfg", clusters=["w1"]),
        {"w1": cluster}, worker_lost_timeout=60.0)
    ctl.start_watches(poll_timeout=1.0)
    return worker, server, manager, client, cluster, ctl, port


def test_watch_pushes_admission_and_finish_without_polling():
    worker, server, manager, client, cluster, ctl, _ = setup_watch_pair()
    try:
        manager.create_workload(Workload(
            name="job", queue_name="lq",
            pod_sets=[PodSet(name="main", count=1,
                             requests={"cpu": 1000})]))
        manager.schedule_once()          # quota reserved on the manager
        ctl.reconcile()                  # nominate -> mirror on worker
        assert wait_for(lambda: "default/job" in worker.workloads)

        # steady state with NO worker events: reconcile must not poll
        base = client.get_calls
        for _ in range(5):
            ctl.reconcile()
        assert client.get_calls == base, \
            "reconcile polled the worker with no events pending"

        worker.schedule_once()           # worker admits -> event pushed
        assert wait_for(lambda: not cluster.watch.events.empty())
        ctl.reconcile()                  # drains the event, targeted sync
        st = manager.workloads["default/job"].admission_check_states["mk"]
        assert st.state == AdmissionCheckState.READY
        assert client.get_calls == base + 1, \
            "event-driven sync should cost exactly one targeted GET"

        # worker-side finish reaches the manager the same way
        worker.finish_workload("default/job")
        assert wait_for(lambda: not cluster.watch.events.empty())
        base = client.get_calls
        ctl.reconcile()
        assert manager.workloads["default/job"].is_finished
        assert client.get_calls <= base + 2
    finally:
        ctl.stop_watches()
        server.stop()


def test_watch_reconnect_replays_missed_events():
    worker, server, manager, client, cluster, ctl, port = setup_watch_pair()
    try:
        manager.create_workload(Workload(
            name="job", queue_name="lq",
            pod_sets=[PodSet(name="main", count=1,
                             requests={"cpu": 1000})]))
        manager.schedule_once()
        ctl.reconcile()
        assert wait_for(lambda: "default/job" in worker.workloads)
        worker.schedule_once()
        assert wait_for(lambda: not cluster.watch.events.empty())
        ctl.reconcile()
        assert (manager.workloads["default/job"]
                .admission_check_states["mk"].state
                == AdmissionCheckState.READY)

        # sever the transport; the worker keeps running and FINISHES the
        # workload while unreachable — those events must replay
        server.stop()
        assert wait_for(lambda: not cluster.watch.events.empty(),
                        timeout=15.0)
        ctl.reconcile()                  # __lost__ marker -> cluster lost
        assert not cluster.active
        worker.finish_workload("default/job")

        server2 = WorkerServer(worker, port=port)
        server2.start()
        try:
            # the watch loop reconnects from its resume token and
            # replays the missed Finished event
            # (a restarted server has a fresh epoch, so a __resync__
            # marker precedes __reconnected__ by one poll: reconcile
            # until the second has been seen, not once after the first)
            assert wait_for(
                lambda: (ctl.reconcile() or cluster.active), timeout=30.0
            ), "reconnect marker must restore the cluster"
            assert wait_for(
                lambda: (ctl.reconcile()
                         or manager.workloads["default/job"].is_finished),
                timeout=10.0)
        finally:
            server2.stop()
    finally:
        ctl.stop_watches()
        server.httpd.server_close()