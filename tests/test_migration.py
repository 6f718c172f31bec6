"""Cross-host elastic state motion over hardened P2P streams.

The reference's recovery story ends at "communicator FAILED, job dead";
PR 6/7 closed the single-host loop. These tests pin the multi-host half
(docs/ELASTIC.md § Multi-host recovery): a piece that survives only on
another host moves over the REAL gRPC stream data plane — CRC32C frame
validation, resumable offsets after a dropped StreamSend, bounded
retries — and falls back to the coordinated checkpoint restore exactly
when streams cannot deliver. "Another host" is simulated two ways, both
in one process tree: a second device server (unit tests) and the
``non_addressable`` device-id quarantine in ``elastic._pull_host_state``
(integration tests); the chaos CLI (`--migration`) drives the same
protocol against a subprocess donor in CI.
"""

import os
import time

import grpc
import numpy as np
import optax
import pytest

from dsml_tpu import obs
from dsml_tpu.comm.device_server import serve_device
from dsml_tpu.comm.migration import (
    MIGRATE_CHUNK,
    MigrationConfig,
    MigrationError,
    ShardMigrator,
    StateDonor,
    payload_chunk_crcs,
    tree_path_str,
)
from dsml_tpu.comm.proto import gpu_sim_pb2 as pb
from dsml_tpu.models.gpt2 import GPT2, GPT2Config
from dsml_tpu.runtime import chaos
from dsml_tpu.runtime.native import _crc32c_py, crc32c


# ---------------------------------------------------------------------------
# CRC32C — the frame checksum (C kernel + bit-identical Python fallback)
# ---------------------------------------------------------------------------


def test_crc32c_known_vectors():
    # RFC 3720 §B.4 check value and the empty string
    assert crc32c(b"") == 0
    assert crc32c(b"123456789") == 0xE3069283
    assert _crc32c_py(b"") == 0
    assert _crc32c_py(b"123456789") == 0xE3069283


def test_crc32c_rolling_equals_one_shot_and_fallback_matches():
    rng = np.random.default_rng(0)
    blob = rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    rolling = 0
    for off in range(0, len(blob), 7_777):
        rolling = crc32c(blob[off : off + 7_777], rolling)
    assert rolling == crc32c(blob) == _crc32c_py(blob)


def test_payload_chunk_crcs_frames_at_absolute_offsets():
    rng = np.random.default_rng(1)
    payload = rng.integers(0, 256, MIGRATE_CHUNK + 100, dtype=np.uint8).tobytes()
    crcs = payload_chunk_crcs(payload)
    assert crcs == [crc32c(payload[:MIGRATE_CHUNK]), crc32c(payload[MIGRATE_CHUNK:])]
    assert payload_chunk_crcs(b"") == [crc32c(b"")]


def test_tree_path_str_dicts_lists_and_optax_state():
    import jax

    tree = {"layers": [{"w": np.zeros(2)}, {"w": np.ones(2)}], "b": np.zeros(1)}
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    keys = {tree_path_str("params", p) for p, _ in flat}
    assert keys == {"params/b", "params/layers/0/w", "params/layers/1/w"}
    # optax adam state (tuple of namedtuples) flattens to stable keys too
    opt = optax.adam(1e-3)
    state = opt.init({"w": np.zeros(3, np.float32)})
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    okeys = {tree_path_str("opt_state", p) for p, _ in flat}
    assert "opt_state/0/mu/w" in okeys and "opt_state/0/nu/w" in okeys


# ---------------------------------------------------------------------------
# wire-fault plan parsing
# ---------------------------------------------------------------------------


def test_wire_fault_plan_parse_and_matching():
    plan = chaos.WireFaultPlan.parse("drop@1;corrupt@3;delay@*,dst=1,s=0.25")
    assert [f.action for f in plan.faults] == ["drop", "corrupt", "delay"]
    assert plan.faults[0].nth == 1 and plan.faults[2].nth is None
    assert plan.faults[2].dst == 1 and plan.faults[2].delay_s == 0.25
    # ordinal counting: send #1 drops, #2 (wrong dst) clean, #3 corrupts
    assert plan.on_send(0, 2).action == "drop"
    assert plan.on_send(0, 2) is None
    assert plan.on_send(0, 2).action == "corrupt"
    # every-send fault keeps firing on its link
    assert plan.on_send(0, 1).action == "delay"
    assert plan.on_send(0, 1).action == "delay"
    assert len(plan.fired) == 4


def test_wire_fault_plan_rejects_bad_specs():
    with pytest.raises(ValueError):
        chaos.WireFaultPlan.parse("explode@1")
    with pytest.raises(ValueError):
        chaos.WireFaultPlan.parse("drop-1")
    with pytest.raises(ValueError):
        chaos.WireFaultPlan.parse("drop@1,unknown=3")


def test_corrupt_fault_flips_exactly_one_byte():
    fault = chaos.WireFault("corrupt")
    payload = bytes(range(256))
    mutated = fault.apply_payload(payload)
    assert mutated != payload and len(mutated) == len(payload)
    assert sum(a != b for a, b in zip(mutated, payload)) == 1


# ---------------------------------------------------------------------------
# device-server stream hardening (GC, gauges, stall, partial harvest)
# ---------------------------------------------------------------------------


@pytest.fixture()
def two_servers():
    recv = serve_device(201, mem_size=0x200000)
    donor = serve_device(202, mem_size=0x200000)
    peers = {0: recv.address, 1: donor.address}
    recv.runtime.configure_peers(peers, 0)
    donor.runtime.configure_peers(peers, 1)
    try:
        yield recv, donor
    finally:
        chaos.set_wire_fault_plan(None)
        recv.stop()
        donor.stop()


def _wait_terminal(rt, sid, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if rt.stream_status(sid) != pb.IN_PROGRESS:
            return rt.stream_status(sid)
        time.sleep(0.01)
    raise TimeoutError(f"stream {sid} still IN_PROGRESS")


def test_stream_table_ttl_gc_and_metrics(two_servers, monkeypatch):
    """ISSUE 8 satellite: terminal StreamState entries used to accumulate
    for the life of the process — they are TTL-evicted now, and the table
    exports comm_streams_active + comm_stream_failures_total."""
    recv, donor = two_servers
    obs.enable(forensics=False)
    try:
        reg = obs.get_registry()
        fails = reg.counter(
            "comm_stream_failures_total",
            "P2P streams that ended FAILED", labels=("device",),
        )
        before = fails.value(device=201)
        donor.runtime.memory.write(0x1000, b"x" * 64)
        sid = donor.runtime.begin_send(0x1000, 64, 0)
        recv.runtime.begin_receive(sid, 0x1000, 64, 1)
        assert _wait_terminal(recv.runtime, sid) == pb.SUCCESS
        assert sid in recv.runtime.streams
        # a FAILED stream counts into the failure counter (length mismatch)
        sid2 = donor.runtime.begin_send(0x1000, 64, 0)
        recv.runtime.begin_receive(sid2, 0x1000, 32, 1)  # armed short
        assert _wait_terminal(recv.runtime, sid2) == pb.FAILED
        assert fails.value(device=201) == before + 1
        # TTL eviction: with a microscopic TTL both terminal entries go
        monkeypatch.setenv("DSML_STREAM_TTL_S", "0.01")
        time.sleep(0.05)
        recv.runtime._gc_streams()
        assert sid not in recv.runtime.streams
        assert sid2 not in recv.runtime.streams
        active = reg.gauge(
            "comm_streams_active",
            "P2P streams not yet terminal", labels=("device",),
        )
        assert active.value(device=201) == 0
    finally:
        obs.disable()


def test_stalled_armed_stream_fails_instead_of_hanging(two_servers, monkeypatch):
    """A dropped StreamSend used to leave the armed receiver IN_PROGRESS
    forever; past DSML_STREAM_STALL_S the status query now returns FAILED."""
    recv, _ = two_servers
    recv.runtime.begin_receive(999_001, 0x1000, 128, 1)  # nothing will arrive
    assert recv.runtime.stream_status(999_001) == pb.IN_PROGRESS
    monkeypatch.setenv("DSML_STREAM_STALL_S", "0.01")
    time.sleep(0.05)
    assert recv.runtime.stream_status(999_001) == pb.FAILED
    assert "stalled" in recv.runtime.streams[999_001].fail_reason


def test_take_partial_harvests_prefix_and_fails_stream(two_servers):
    recv, _ = two_servers
    recv.runtime.begin_receive(999_002, 0x1000, 100, 1)
    with recv.runtime._stream_lock:
        st = recv.runtime.streams[999_002]
        st.chunks.append(b"abc")
        st.received = 3
    assert recv.runtime.take_partial(999_002) == b"abc"
    assert recv.runtime.stream_status(999_002) == pb.FAILED


def test_late_delivery_on_terminal_stream_never_writes(two_servers):
    """Review fix pin: a payload arriving AFTER the stream went terminal
    (stall verdict / take_partial harvest) must NOT write to recv_addr —
    the migrator may have re-armed that landing address for its next
    piece. A new StreamSend call on a terminal id opens a FRESH, UNARMED
    stream (the recycled-id rule): its bytes stay buffered, never land."""
    recv, _ = two_servers
    recv.runtime.memory.write(0x1000, b"N" * 8)  # the next piece's payload
    recv.runtime.begin_receive(999_003, 0x1000, 8, 1)
    assert recv.runtime.take_partial(999_003) == b""  # harvested: terminal

    class _Chunk:
        streamId = 999_003
        data = b"STALEOLD"

    recv.runtime.receive_chunks([_Chunk()])
    st = recv.runtime.streams[999_003]
    assert st.status == pb.IN_PROGRESS and not st.armed  # fresh, buffered
    assert recv.runtime.read_bytes(0x1000, 8) == b"N" * 8  # untouched


def test_begin_receive_replaces_terminal_recycled_stream_id(two_servers):
    """Regression pin for the recycled-id hole: arming a stream id that a
    restarted sender reused must start a FRESH stream, not hand back the
    old terminal entry's stale state."""
    recv, donor = two_servers
    donor.runtime.memory.write(0x1000, b"y" * 64)
    sid = donor.runtime.begin_send(0x1000, 64, 0)
    recv.runtime.begin_receive(sid, 0x1000, 64, 1)
    assert _wait_terminal(recv.runtime, sid) == pb.SUCCESS
    # "restarted" sender reuses the id for a DIFFERENT 32-byte stream
    recv.runtime.begin_receive(sid, 0x1100, 32, 1)
    st = recv.runtime.streams[sid]
    assert st.status == pb.IN_PROGRESS and st.received == 0
    assert st.num_bytes == 32 and st.recv_addr == 0x1100


# ---------------------------------------------------------------------------
# donor ⇄ migrator round-trip over real gRPC streams
# ---------------------------------------------------------------------------


def _migrator(recv, donor, **cfg_kw) -> ShardMigrator:
    cfg_kw.setdefault("timeout_s", 10.0)
    return ShardMigrator(
        recv.runtime, 0, [(1, donor.address)],
        config=MigrationConfig(**cfg_kw), local_address=recv.address,
    )


def test_fetch_piece_round_trip_bit_exact(two_servers):
    recv, donor = two_servers
    arr = np.arange(48_000, dtype=np.float32).reshape(120, 400)
    donor.runtime.donor.register_array("params/w", arr)
    mig = _migrator(recv, donor)
    got = mig.fetch_piece("params/w", ((30, 90), (100, 300)), "float32")
    np.testing.assert_array_equal(got, arr[30:90, 100:300])
    assert mig.stats["pieces"] == 1
    assert mig.stats["bytes"] == 60 * 200 * 4
    mig.close()


def test_dropped_stream_resumes_from_offset(two_servers):
    """One dropped StreamSend: the delivered prefix is harvested and only
    the remainder re-ships — same bits, resumed (not restarted)."""
    recv, donor = two_servers
    arr = np.arange(200_000, dtype=np.float32)
    donor.runtime.donor.register_array("w", arr)
    chaos.set_wire_fault_plan(chaos.WireFaultPlan.parse("drop@1"))
    mig = _migrator(recv, donor)
    got = mig.fetch_piece("w", ((0, 200_000),), "float32")
    np.testing.assert_array_equal(got, arr)
    assert mig.stats["resumed"] == 1
    assert mig.stats["integrity_failures"] == 0
    mig.close()


def test_corrupt_chunk_fires_crc_and_aborts(two_servers):
    """Persistent corruption: every attempt fails frame validation, the
    piece is declared undeliverable, and the corrupt bytes never reach the
    caller — zero silent corruption."""
    recv, donor = two_servers
    arr = np.arange(10_000, dtype=np.float32)
    donor.runtime.donor.register_array("w", arr)
    chaos.set_wire_fault_plan(chaos.WireFaultPlan.parse("corrupt@*"))
    obs.enable(forensics=False)
    try:
        reg = obs.get_registry()
        counter = reg.counter(
            "comm_stream_integrity_failures_total",
            "comm stream integrity failures total",
        )
        before = counter.value()
        mig = _migrator(recv, donor, retries=1)
        with pytest.raises(MigrationError, match="CRC32C mismatch"):
            mig.fetch_piece("w", ((0, 10_000),), "float32")
        assert mig.stats["integrity_failures"] == 2  # 1 attempt + 1 retry
        assert counter.value() - before == 2
        mig.close()
    finally:
        obs.disable()


def test_transient_corruption_retries_to_success(two_servers):
    """A fault that hits exactly one send: the CRC abort triggers a
    whole-piece retry that succeeds — hardening, not fragility."""
    recv, donor = two_servers
    arr = np.arange(5_000, dtype=np.float32)
    donor.runtime.donor.register_array("w", arr)
    chaos.set_wire_fault_plan(chaos.WireFaultPlan.parse("corrupt@1"))
    mig = _migrator(recv, donor, retries=2)
    got = mig.fetch_piece("w", ((0, 5_000),), "float32")
    np.testing.assert_array_equal(got, arr)
    assert mig.stats["integrity_failures"] == 1
    assert mig.stats["retries"] == 1
    mig.close()


def test_unknown_key_and_dead_donor_raise_migration_error(two_servers):
    recv, donor = two_servers
    mig = _migrator(recv, donor)
    with pytest.raises(MigrationError, match="no live donor"):
        mig.fetch_piece("nope/missing", ((0, 1),), "float32")
    mig.close()
    # a donor that is gone entirely: unreachable endpoint
    dead = ShardMigrator(
        recv.runtime, 0, [(1, "127.0.0.1:1")],
        config=MigrationConfig(timeout_s=2.0, retries=0),
        local_address=recv.address,
    )
    with pytest.raises(MigrationError, match="no live donor"):
        dead.fetch_piece("w", ((0, 1),), "float32")
    dead.close()


def test_donor_selection_skips_non_holders(two_servers):
    """Donor selection is per piece: the migrator asks each donor what it
    holds and routes to the one that has the leaf."""
    recv, donor = two_servers
    empty = serve_device(203, mem_size=0x20000)
    try:
        arr = np.arange(100, dtype=np.float32)
        donor.runtime.donor.register_array("w", arr)
        mig = ShardMigrator(
            recv.runtime, 0, [(2, empty.address), (1, donor.address)],
            config=MigrationConfig(timeout_s=10.0),
            local_address=recv.address,
        )
        got = mig.fetch_piece("w", ((0, 100),), "float32")
        np.testing.assert_array_equal(got, arr)
        mig.close()
    finally:
        empty.stop()


def test_state_donor_register_state_keys_and_plan():
    import jax

    rt_handle = serve_device(204, mem_size=0x40000)
    try:
        donor = rt_handle.runtime.donor
        tree = {"layers": [{"w": np.ones((2, 2), np.float32)}],
                "b": np.zeros(3, np.float32)}
        n = donor.register_state(tree, "params")
        assert n == 2
        plan = donor.plan(["params/b", "params/layers/0/w", "params/nope"])
        assert plan["params/b"] == {"shape": [3], "dtype": "float32",
                                    "version": None}
        assert plan["params/layers/0/w"]["shape"] == [2, 2]
        assert plan["params/nope"] is None
        del jax  # imported for parity with register_state's device_get path
    finally:
        rt_handle.stop()


# ---------------------------------------------------------------------------
# data-plane arm RPCs ride call_with_retries (client satellite)
# ---------------------------------------------------------------------------


class _Err(grpc.RpcError):
    def __init__(self, code):
        self._code = code

    def code(self):
        return self._code

    def details(self):
        return "synthetic"


class _FlakyDevice:
    """Device stub whose arm RPCs flake N times, then answer."""

    def __init__(self, n_failures):
        self.n = n_failures
        self.calls = 0

    def _maybe_fail(self):
        self.calls += 1
        if self.n > 0:
            self.n -= 1
            raise _Err(grpc.StatusCode.UNAVAILABLE)

    def BeginSend(self, request, timeout=None):  # noqa: N802
        self._maybe_fail()
        return pb.BeginSendResponse(initiated=True,
                                    streamId=pb.StreamId(value=77))

    def BeginReceive(self, request, timeout=None):  # noqa: N802
        self._maybe_fail()
        return pb.BeginReceiveResponse(initiated=True)

    def GetStreamStatus(self, request, timeout=None):  # noqa: N802
        self._maybe_fail()
        return pb.GetStreamStatusResponse(status=pb.SUCCESS)


def test_data_plane_arm_rpcs_retry_transient_flakes():
    """ISSUE 8 satellite: BeginSend/BeginReceive/GetStreamStatus retry
    UNAVAILABLE/DEADLINE_EXCEEDED like the control-plane ops do."""
    from dsml_tpu.comm.client import PipelineClient

    flaky = _FlakyDevice(2)
    client = PipelineClient(coordinator=None, devices=[flaky], comm_id=1,
                            device_ids=[5])
    assert client.begin_send(0, 0x1000, 64, 1) == 77
    assert flaky.calls == 3  # 2 flakes + 1 answer
    flaky.n = 1
    client.begin_receive(0, 77, 0x1000, 64, 1)
    flaky.n = 1
    assert client.stream_status(0, 77) == pb.SUCCESS


def test_data_plane_arm_rpcs_do_not_retry_real_answers():
    from dsml_tpu.comm.client import PipelineClient

    class _NotFound:
        calls = 0

        def GetStreamStatus(self, request, timeout=None):  # noqa: N802
            self.calls += 1
            raise _Err(grpc.StatusCode.NOT_FOUND)

    stub = _NotFound()
    client = PipelineClient(coordinator=None, devices=[stub], comm_id=1,
                            device_ids=[5])
    with pytest.raises(grpc.RpcError):
        client.stream_status(0, 123)
    assert stub.calls == 1


def test_stale_donor_version_is_refused(two_servers):
    """CRCs prove bytes match the donor's snapshot, not that the snapshot
    is the right STEP: a receiver pinning expect_version refuses a donor
    serving any other version instead of landing stale bytes."""
    recv, donor = two_servers
    arr = np.arange(64, dtype=np.float32)
    donor.runtime.donor.register_array("w", arr)
    donor.runtime.donor.version = 7
    stale = ShardMigrator(
        recv.runtime, 0, [(1, donor.address)],
        config=MigrationConfig(timeout_s=10.0), local_address=recv.address,
        expect_version=8,
    )
    with pytest.raises(MigrationError, match="no live donor"):
        stale.fetch_piece("w", ((0, 64),), "float32")
    stale.close()
    fresh = ShardMigrator(
        recv.runtime, 0, [(1, donor.address)],
        config=MigrationConfig(timeout_s=10.0), local_address=recv.address,
        expect_version=7,
    )
    np.testing.assert_array_equal(
        fresh.fetch_piece("w", ((0, 64),), "float32"), arr
    )
    fresh.close()


def test_reset_donors_revives_flaked_donor_and_clears_plans(two_servers):
    """A transient donor outage must not permanently disable migration:
    reset_donors (called per recovery by the controller) forgets death
    verdicts and cached plans."""
    recv, donor = two_servers
    arr = np.arange(32, dtype=np.float32)
    donor.runtime.donor.register_array("w", arr)
    mig = _migrator(recv, donor)
    mig._donors[0].alive = False
    mig._plans[(donor.address, "w")] = False
    with pytest.raises(MigrationError, match="no live donor"):
        mig.fetch_piece("w", ((0, 32),), "float32")
    mig.reset_donors()
    np.testing.assert_array_equal(
        mig.fetch_piece("w", ((0, 32),), "float32"), arr
    )
    mig.close()


def test_stage_allocator_never_clobbers_inflight_sends():
    """A staging wrap must not overwrite a payload whose background push
    has not read it yet: allocations overlapping a live staged range raise
    RESOURCE_EXHAUSTED instead of corrupting the in-flight send."""
    from dsml_tpu.comm.device_server import DeviceError, StreamState

    handle = serve_device(209, mem_size=0x1000)  # staging half = 0x800
    try:
        donor = handle.runtime.donor
        addr, token = donor._stage(0x700)
        # even BEFORE the stream id is known, the reservation itself blocks
        # a concurrent wrap (two BeginMigrations racing the allocator)
        with pytest.raises(DeviceError, match="in-flight"):
            donor._stage(0x700)
        # committed to a still-IN_PROGRESS stream: still blocked
        handle.runtime.streams[12345] = StreamState(12345)
        donor._commit_stage(token, 12345)
        with pytest.raises(DeviceError, match="in-flight"):
            donor._stage(0x700)
        # a single piece larger than the whole staging area is refused too
        with pytest.raises(DeviceError, match="exceeds the staging area"):
            donor._stage(0x2000)
        # once the stream goes terminal the range is reusable
        handle.runtime.streams[12345].status = 2  # pb.FAILED
        assert donor._stage(0x700)[0] == addr
    finally:
        # that last reservation is never sent: drop it, or it stays claimed as
        # migration_staging on the process-wide memory ledger until the donor is
        # collected, and the chaos smoke's leak audit (test_controller.py) fails
        # when xdist runs it after this file on the same worker
        handle.runtime.donor._live_stages.clear()
        handle.stop()


def test_dtype_shape_mismatch_is_migration_error_not_crash(two_servers):
    """CRCs validate transport, not semantics: a donor serving the leaf at
    a different dtype must be refused as a MigrationError (the controller's
    fallback trigger) — same-itemsize reinterpretation would otherwise land
    garbage silently, different-itemsize would crash the recovery."""
    recv, donor = two_servers
    donor.runtime.donor.register_array(
        "w", np.arange(64, dtype=np.float64)  # donor holds f64
    )
    mig = _migrator(recv, donor, retries=0)
    with pytest.raises(MigrationError, match="expected float32"):
        mig.fetch_piece("w", ((0, 64),), "float32")
    mig.close()


def test_recycled_stream_id_chunks_before_arm_starts_fresh(two_servers):
    """Chunks-first half of the recycled-id regression: a restarted
    sender's pushes usually land BEFORE the receiver's BeginReceive — the
    first chunk on a terminal id must open a FRESH stream, not append to
    the stale entry (whose SUCCESS would falsely ack the delivery)."""
    recv, donor = two_servers
    donor.runtime.memory.write(0x1000, b"a" * 16)
    sid = donor.runtime.begin_send(0x1000, 16, 0)
    recv.runtime.begin_receive(sid, 0x1000, 16, 1)
    assert _wait_terminal(recv.runtime, sid) == pb.SUCCESS

    class _Chunk:
        streamId = sid
        data = b"NEWPAYLOAD_16BYT"

    assert recv.runtime.receive_chunks([_Chunk()]) is True  # buffered, unarmed
    st = recv.runtime.streams[sid]
    assert st.status == pb.IN_PROGRESS and st.received == 16
    recv.runtime.begin_receive(sid, 0x1100, 16, 1)  # late arm completes it
    assert recv.runtime.stream_status(sid) == pb.SUCCESS
    assert recv.runtime.read_bytes(0x1100, 16) == b"NEWPAYLOAD_16BYT"


def test_decode_fleet_failed_factory_returns_devices(devices8):
    """A replica factory that raises must return its chip span to the pool
    — nothing will ever retire that rid, so leaking would permanently
    shrink capacity."""
    from dsml_tpu.runtime.controller import DecodeFleet

    fleet = DecodeFleet(
        _PoolReplica, min_replicas=1, max_replicas=3,
        devices=devices8[:4], devices_per_replica=2,
        scale_down_idle_ticks=10_000,
    )
    assert len(fleet._device_pool) == 2

    def boom(devices):
        raise RuntimeError("factory OOM")

    fleet._make = boom
    with pytest.raises(RuntimeError, match="factory OOM"):
        fleet._spawn("scale_up")
    assert len(fleet._device_pool) == 2  # span returned
    fleet._make = _PoolReplica
    rid = fleet._spawn("retry")  # pool intact: the retry succeeds
    assert len(fleet._replica_devices[rid]) == 2


def test_from_comm_resolves_membership(two_servers):
    """The client-side membership resolver: this host's entry (by device
    id or bound address) becomes self_rank, every other entry a donor."""
    recv, donor = two_servers
    members = [(0, recv.runtime.device_id, recv.address),
               (1, donor.runtime.device_id, donor.address)]
    arr = np.arange(16, dtype=np.float32)
    donor.runtime.donor.register_array("w", arr)
    mig = ShardMigrator.from_comm(members, recv.runtime,
                                  config=MigrationConfig(timeout_s=10.0))
    assert mig.self_rank == 0
    np.testing.assert_array_equal(
        mig.fetch_piece("w", ((0, 16),), "float32"), arr
    )
    mig.close()
    with pytest.raises(ValueError, match="not in the membership table"):
        ShardMigrator.from_comm([(0, 999_999, "nowhere:1")], recv.runtime)


# ---------------------------------------------------------------------------
# coordinator brokering + coordinated-fallback step agreement
# ---------------------------------------------------------------------------


def test_broker_migration_resolves_self_and_donors(two_servers):
    from dsml_tpu.comm.coordinator import CoordinatorConfig, CoordinatorRuntime

    recv, donor = two_servers
    rt = CoordinatorRuntime(CoordinatorConfig(health_interval_s=3600.0))
    try:
        comm = rt.comm_init(2, [recv.address, donor.address])
        self_rank, donors = rt.broker_migration(
            comm.comm_id, recv.runtime.device_id
        )
        assert self_rank == 0
        assert donors == [(1, donor.address)]
        from dsml_tpu.comm.device_server import DeviceError

        with pytest.raises(DeviceError):
            rt.broker_migration(comm.comm_id, 12345)
    finally:
        rt.stop()


def test_newest_common_step_agreement():
    from dsml_tpu.checkpoint import CheckpointManager

    assert CheckpointManager.newest_common_step([[2, 4, 6], [4, 6], [2, 4]]) == 4
    assert CheckpointManager.newest_common_step([[2, 4], []]) is None
    assert CheckpointManager.newest_common_step([]) is None
    assert CheckpointManager.newest_common_step([[8], [6]]) is None


# ---------------------------------------------------------------------------
# elastic integration: the torn-refusal ⇄ migration conversion (virtual-8)
# ---------------------------------------------------------------------------


def _hybrid_state(devices8):
    """[dp=4, tp=2] state after one step, declared shardings re-pinned —
    device i holds tp rank i%2, so {1,3} are the LOCAL tp-1 holders and
    {5,7} the 'remote' ones once 4..7 play host B."""
    import jax
    import optax.tree_utils as otu
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dsml_tpu.parallel.hybrid import (
        init_hybrid,
        make_hybrid_train_step,
        shard_params,
    )
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    opt = optax.adam(1e-2)
    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.vocab_size, (8, cfg.max_seq)).astype(np.int32)
    y = np.roll(x, -1, 1).astype(np.int32)
    mesh8 = build_mesh(MeshSpec(dp=4, sp=1, tp=2), devices8)
    step = make_hybrid_train_step(model, opt, mesh8, attn_impl="ring")
    params, opt_state = init_hybrid(model, opt, mesh8, seed=0)
    params, opt_state, _ = step(params, opt_state, x, y)
    pspecs = model.param_specs()
    params = shard_params(params, mesh8, pspecs)
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh8, s), pspecs,
                            is_leaf=lambda s: isinstance(s, P))
    repl = NamedSharding(mesh8, P())
    opt_state = otu.tree_map_params(
        opt, lambda l, sh: jax.device_put(l, sh), opt_state, param_sh,
        transform_non_params=lambda l: jax.device_put(l, repl),
    )
    return model, opt, params, opt_state, (x, y)


@pytest.fixture(scope="module")
def hybrid_state(devices8):
    return _hybrid_state(devices8)


def test_pull_refuses_remote_only_piece_without_migrator(devices8, hybrid_state):
    """ISSUE 8 satellite, direction 1: a piece surviving only on
    non-addressable devices RAISES (never zero-fills) without a migrator."""
    from dsml_tpu.parallel import elastic

    model, opt, params, opt_state, _ = hybrid_state
    lost = [devices8[i] for i in (1, 3)]
    remote = {devices8[i].id for i in (4, 5, 6, 7)}
    with pytest.raises(RuntimeError, match="non-addressable"):
        elastic.reconfigure(
            model, opt, params, opt_state,
            surviving_devices=[devices8[0], devices8[2]],
            lost_devices=lost, non_addressable=remote,
        )


def test_migration_converts_refusal_into_successful_pull(devices8, hybrid_state):
    """ISSUE 8 satellite, direction 2 + tentpole acceptance: the EXACT
    refusal case completes via P2P stream migration — no checkpoint — and
    the pulled state is bit-identical to the pre-failure host values."""
    import jax

    from dsml_tpu.parallel import elastic

    model, opt, params, opt_state, _ = hybrid_state
    ref_host = jax.tree.map(lambda l: np.asarray(jax.device_get(l)), params)

    recv = serve_device(205, mem_size=0x400000)
    donor = serve_device(206, mem_size=0x400000)
    peers = {0: recv.address, 1: donor.address}
    recv.runtime.configure_peers(peers, 0)
    donor.runtime.configure_peers(peers, 1)
    try:
        donor.runtime.donor.register_state(params, "params")
        donor.runtime.donor.register_state(opt_state, "opt_state")
        mig = ShardMigrator(
            recv.runtime, 0, [(1, donor.address)],
            config=MigrationConfig(timeout_s=30.0),
            local_address=recv.address,
        )
        lost = [devices8[i] for i in (1, 3)]
        remote = {devices8[i].id for i in (4, 5, 6, 7)}
        state = elastic.reconfigure(
            model, opt, params, opt_state,
            surviving_devices=[devices8[0], devices8[2]],
            lost_devices=lost, non_addressable=remote, migrator=mig,
        )
        assert mig.stats["pieces"] > 0 and mig.stats["bytes"] > 0
        got = jax.tree.map(lambda l: np.asarray(jax.device_get(l)), state.params)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref_host)):
            np.testing.assert_array_equal(a, b)
        mig.close()
    finally:
        recv.stop()
        donor.stop()


@pytest.mark.slow
def test_controller_orchestrates_migration_and_corrupt_fallback(
    devices8, tmp_path
):
    """The controller leg end-to-end: a shrink whose tp-1 shard survives
    only remotely recovers via kind="reconfigure" with migration stats in
    the recovery record; the SAME failure over a corrupted link falls back
    to kind="checkpoint_fallback" (CRC named in the reason), zero silent
    corruption."""
    from dsml_tpu.parallel.mesh import MeshSpec, build_mesh
    from dsml_tpu.runtime.controller import (
        ControllerConfig,
        DeviceLost,
        ElasticController,
    )

    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    opt = optax.adam(1e-2)
    global_batch = 8
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size,
                        (8, global_batch, cfg.max_seq)).astype(np.int32)

    def provider(step):
        x = data[step - 1]
        return x, np.roll(x, -1, 1).astype(np.int32)

    spec = MeshSpec(dp=4, sp=1, tp=2)
    remote = frozenset(devices8[i].id for i in (4, 5, 6, 7))

    recv = serve_device(207, mem_size=0x400000)
    donor = serve_device(208, mem_size=0x400000)
    peers = {0: recv.address, 1: donor.address}
    recv.runtime.configure_peers(peers, 0)
    donor.runtime.configure_peers(peers, 1)
    try:
        def one_run(wire_spec, name):
            chaos.set_wire_fault_plan(
                chaos.WireFaultPlan.parse(wire_spec) if wire_spec else None
            )
            mig = ShardMigrator(
                recv.runtime, 0, [(1, donor.address)],
                config=MigrationConfig(timeout_s=30.0, retries=1),
                local_address=recv.address,
            )
            fleet = chaos.VirtualFleet(devices8)
            ctl = ElasticController(
                model, opt, provider,
                checkpoint_dir=str(tmp_path / name),
                fleet=fleet, mesh=build_mesh(spec, devices8), spec=spec,
                config=ControllerConfig(checkpoint_every=2, growback="keep"),
                global_batch=global_batch, seed=0,
                migrator=mig, non_addressable=remote,
            )

            def on_step(s):
                if s == 3:
                    # donor snapshot AT the failure point: host B's live view
                    donor.runtime.donor.register_state(ctl.params, "params")
                    donor.runtime.donor.register_state(ctl.opt_state, "opt_state")
                    dead = fleet.kill(1, 3)
                    ctl.inject(DeviceLost(dead, "local tp-1 holders"))

            with ctl:
                report = ctl.run(4, on_step=on_step)
            chaos.set_wire_fault_plan(None)
            return report, mig

        report, mig = one_run("", "clean")
        rec = report["recoveries"][0]
        assert rec["kind"] == "reconfigure"
        assert rec["migrated_bytes"] > 0 and rec["migrated_pieces"] > 0
        assert rec["lost_steps"] == 0  # no checkpoint rewind
        assert report["steps_completed"] == 4
        mig.close()

        report, mig = one_run("corrupt@*", "corrupt")
        rec = report["recoveries"][0]
        assert rec["kind"] == "checkpoint_fallback"
        assert "CRC" in rec["fallback_reason"]
        assert rec["migration_integrity_failures"] > 0
        assert report["steps_completed"] == 4
        mig.close()
    finally:
        chaos.set_wire_fault_plan(None)
        recv.stop()
        donor.stop()


# ---------------------------------------------------------------------------
# DecodeFleet device pool: replicas spanning multiple devices
# ---------------------------------------------------------------------------


class _PoolReplica:
    """Zero-compute replica that records the devices it was handed."""

    n_slots = 2

    def __init__(self, devices):
        self.devices = tuple(devices)
        self._queue = []
        self._done = {}
        self._next = 0
        self.obs_replica = "0"

    @property
    def n_queued(self):
        return len(self._queue)

    n_active = 0
    n_pending = 0

    def submit(self, prompt, max_new):
        rid = self._next
        self._next += 1
        self._queue.append((rid, list(np.asarray(prompt))))
        return rid

    def step(self):
        if self._queue:
            rid, toks = self._queue.pop(0)
            self._done[rid] = toks

    def collect(self):
        out, self._done = self._done, {}
        return out

    def abandon(self):
        class _Req:
            def __init__(self, rid):
                self.rid = rid

        out = [_Req(rid) for rid, _ in self._queue]
        self._queue = []
        return out


def test_decode_fleet_device_pool_assignment_and_return(devices8):
    from dsml_tpu.runtime.controller import DecodeFleet

    spans = []

    def make(devices):
        replica = _PoolReplica(devices)
        spans.append(replica.devices)
        return replica

    fleet = DecodeFleet(
        make, min_replicas=2, max_replicas=8, devices=devices8[:6],
        devices_per_replica=2, scale_down_idle_ticks=10_000,
    )
    # capacity caps max_replicas: 6 devices / 2 per replica = 3
    assert fleet.max_replicas == 3
    assert fleet.n_replicas == 2
    assert len(spans) == 2 and len(set(spans[0]) & set(spans[1])) == 0
    assert all(len(s) == 2 for s in spans)
    # a killed replica returns its chips; the respawn reuses them
    killed_span = fleet._replica_devices[0]
    fleet.submit([1, 2, 3], 4)
    fleet.kill_replica(0)
    assert set(killed_span) <= set(fleet._device_pool)
    fleet.tick()  # dispatches the requeued work onto a survivor
    results = fleet.run()
    assert list(results.values()) == [[1, 2, 3]]


def test_decode_fleet_pool_validates_capacity(devices8):
    from dsml_tpu.runtime.controller import DecodeFleet

    with pytest.raises(ValueError, match="cannot back"):
        DecodeFleet(_PoolReplica, min_replicas=3, devices=devices8[:4],
                    devices_per_replica=2)
    with pytest.raises(ValueError, match="devices_per_replica"):
        DecodeFleet(_PoolReplica, devices=devices8[:4], devices_per_replica=0)


def test_for_devices_multi_device_replica_same_tokens(devices8):
    """ContinuousBatcher.for_devices spans a tp mesh over its device slice
    and decodes the same tokens as the single-device batcher — the fleet's
    multi-device replicas are drop-in."""
    from dsml_tpu.serving import ContinuousBatcher

    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    params = model.init(0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, 5).astype(np.int32)
               for _ in range(2)]
    ref = ContinuousBatcher(model, params, n_slots=2)
    ref_rids = [ref.submit(p, 4) for p in prompts]
    ref_tokens = ref.run()

    srv = ContinuousBatcher.for_devices(model, params, devices8[:2], n_slots=2)
    assert srv.mesh is not None and srv.mesh.shape.get("tp") == 2
    rids = [srv.submit(p, 4) for p in prompts]
    tokens = srv.run()
    for a, b in zip(rids, ref_rids):
        assert tokens[a] == ref_tokens[b]
    # one device keeps the plain single-device batcher
    assert ContinuousBatcher.for_devices(model, params, devices8[:1]).mesh is None


# ---------------------------------------------------------------------------
# env knobs
# ---------------------------------------------------------------------------


def test_migration_config_from_env(monkeypatch):
    monkeypatch.setenv("DSML_MIGRATE_TIMEOUT_S", "7.5")
    monkeypatch.setenv("DSML_MIGRATE_RETRIES", "5")
    monkeypatch.setenv("DSML_MIGRATE_RECV_ADDR", "8192")
    cfg = MigrationConfig.from_env()
    assert cfg.timeout_s == 7.5 and cfg.retries == 5 and cfg.recv_addr == 8192
    monkeypatch.setenv("DSML_MIGRATE_RETRIES", "garbage")
    assert MigrationConfig.from_env().retries == MigrationConfig.retries


def test_wire_fault_plan_from_env(monkeypatch):
    monkeypatch.setenv("DSML_CHAOS_WIRE", "corrupt@2")
    chaos.set_wire_fault_plan(None)
    chaos._WIRE_PLAN = chaos._WIRE_UNSET  # force a re-read
    plan = chaos.wire_fault_plan()
    try:
        assert plan is not None and plan.faults[0].action == "corrupt"
    finally:
        chaos.set_wire_fault_plan(None)


def test_stream_ttl_env_guard():
    from dsml_tpu.comm.device_server import _env_float

    os.environ["_DSML_TEST_FLOAT"] = "not-a-number"
    try:
        assert _env_float("_DSML_TEST_FLOAT", 3.5) == 3.5
    finally:
        del os.environ["_DSML_TEST_FLOAT"]
