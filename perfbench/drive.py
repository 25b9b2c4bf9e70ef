"""Drives the system under test: ``spatialflink_tpu.driver.main`` in this
process, over its in-process ``memory://`` broker, as the reference's
Kafka -> Flink -> Kafka topology.

The program receives only the generated records. Its own graceful stop ends
the run: once the window has closed, the next fetch of an input topic hands
the consumer GeoFlink's control tuple (``HelperClass.checkExitControlTuple``)
in place of the next record, and ``driver.main`` unwinds and returns 0.

The window closes after ``seconds``, or sooner, at the first check at which
the primary input topic holds less than one slide of events unfetched: a
program fast enough to drain the backlog is measured over what it drained,
and never reaches the topic's end (and its end-of-stream flush of partial
windows) inside the window. A program that returns inside the window has
crashed or lost its stream, and the run fails.

Everything measured is read afterwards from the output topic, whose records
carry the broker's append time (``timestamp_ms``): the emit clock.
"""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import threading
import time


BROKER_URL = "memory://perfbench"
CONTROL = json.dumps({"geometry": {"type": "control", "coordinates": []}})
MARKER = "__window_commit__:"


def driver_conf(conf: dict, traffic: dict) -> dict:
    """The program's YAML configuration: the deployment's own, with the
    broker and the query option the traffic asks for."""
    d = json.loads(json.dumps(conf["geoflink_conf"]))
    d["kafkaBootStrapServers"] = BROKER_URL
    d["query"]["option"] = traffic["query_option"]
    return d


class Run:
    """One run of the program; ``window`` is (t_open, t_close) on the host's
    wall clock once the window has closed, and ``closed_by`` says what
    closed it: ``"seconds"`` or ``"backlog"``."""

    def __init__(self, conf: dict, traffic: dict, streams, seconds: float,
                 ready, tracer=None):
        from spatialflink_tpu.streams.kafka import (reset_memory_brokers,
                                                    resolve_broker)

        self.conf, self.traffic = conf, traffic
        self.streams = streams
        self.seconds = seconds
        self.ready = ready          # (run) -> bool: has the warm-up ended?
        self.tracer = tracer
        reset_memory_brokers()      # a fresh broker for every run
        self.broker = resolve_broker(BROKER_URL)
        self.out_topic = conf["geoflink_conf"]["outputStream"]["topicName"]
        self.window = None
        self.closed_by = "seconds"
        self.primary = conf["topic1"]
        # the window closes once the primary topic holds less than this
        self.min_backlog = conf["stream_rate_hz"] * conf["slide_s"]
        self.fetched = {s.topic: 0 for s in streams}
        self.lowered: list = []     # (wall time, program) lowered
        self._stop = threading.Event()
        self._done = threading.Event()
        self._scan = 0
        self.markers: list = []     # (append ms, window start, window end)
        self.backlog_at_open: dict = {}
        self.backlog_at_close: dict = {}
        self.error = None

    # -------------------------------------------------------- transport

    def _wrap_fetch(self):
        from spatialflink_tpu.streams.kafka import BrokerRecord

        real = self.broker.fetch
        inputs = set(self.fetched)

        def fetch(topic, offset, max_records=500):
            if topic not in inputs:
                return real(topic, offset, max_records)
            if self._stop.is_set():
                return [BrokerRecord(offset=offset, key=None, value=CONTROL)]
            out = real(topic, offset, max_records)
            if out:
                self.fetched[topic] = out[-1].offset + 1
            return out

        self.broker.fetch = fetch
        self._real_fetch = real

    def new_markers(self) -> list:
        """Window commit markers appended since the last call."""
        end = self.broker.end_offset(self.out_topic)
        while self._scan < end:
            batch = self._real_fetch(self.out_topic, self._scan, 1 << 16)
            for r in batch:
                if isinstance(r.key, str) and r.key.startswith(MARKER):
                    _job, s, e, _cell = r.key[len(MARKER):].rsplit(":", 3)
                    self.markers.append((r.timestamp_ms, int(s), int(e)))
            self._scan = batch[-1].offset + 1
        return self.markers

    def output(self) -> list:
        """Every record of the output topic."""
        return self._real_fetch(self.out_topic, 0,
                                self.broker.end_offset(self.out_topic))

    def backlog(self) -> dict:
        """Records produced to each input topic and not yet fetched."""
        return {t: self.broker.end_offset(t) - n
                for t, n in self.fetched.items()}

    # ---------------------------------------------------------- control

    def _monitor(self) -> None:
        try:
            while not self.ready(self):
                if self._done.is_set():
                    raise RuntimeError("the program ended before the warm-up")
                time.sleep(0.05)
            self.backlog_at_open = self.backlog()
            if self.tracer is not None:
                self.tracer.start()
            t_open = time.time()
            while time.time() < t_open + self.seconds:
                left = self.backlog()[self.primary]
                if self._done.is_set():
                    raise RuntimeError(
                        f"the program ended inside the window with {left} "
                        f"records of {self.primary!r} unfetched")
                if left < self.min_backlog:
                    self.closed_by = "backlog"
                    break
                time.sleep(min(0.05, max(0.0, t_open + self.seconds
                                         - time.time())))
            t_close = time.time()
            self.window = (t_open, t_close)
            self.backlog_at_close = self.backlog()
            if self.tracer is not None:
                self.tracer.stop()
        except BaseException as e:
            self.error = e
        finally:
            self._stop.set()

    def drive(self, argv: list) -> None:
        """Run ``driver.main(argv)`` in this thread until the window has
        closed and the program has stopped."""
        import jax.monitoring

        from spatialflink_tpu import driver

        def lowered(name, _secs, fun_name="?", **_kw):
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.lowered.append((time.time(), fun_name))

        jax.monitoring.register_event_duration_secs_listener(lowered)
        self._wrap_fetch()
        mon = threading.Thread(target=self._monitor, name="perfbench-window",
                               daemon=True)
        with tempfile.TemporaryDirectory(prefix="perfbench-") as tmp:
            path = os.path.join(tmp, "conf.yml")
            import yaml

            with open(path, "w") as f:
                yaml.safe_dump(driver_conf(self.conf, self.traffic), f)
            mon.start()
            try:
                with open(os.devnull, "w") as null, \
                        contextlib.redirect_stdout(null):
                    rc = driver.main(["--config", path] + argv)
            finally:
                self._done.set()
                self._stop.set()
                mon.join()
                jax.monitoring.unregister_event_duration_listener(lowered)
        if rc != 0:
            raise RuntimeError(f"driver.main exited {rc}")
        if self.error is not None:
            raise self.error

    def lowered_in_window(self) -> list:
        """The programs JAX lowered (to compile them or to load them from
        its cache) inside the window: none, once the warm-up is whole."""
        t0, t1 = self.window
        return [n for t, n in self.lowered if t0 <= t <= t1]


# ----------------------------------------------------------------- warm-up


def full_windows(n: int):
    """Drain warm-up: over once ``n`` windows that the stream fills from
    start to end have been emitted -- every shape is then compiled (the
    half-full first window and the full ones)."""
    from stream import T0

    def ready(run: Run) -> bool:
        return len({s for _t, s, _e in run.new_markers() if s >= T0}) >= n

    return ready
