"""On-demand XLA profiling: bounded captures, downloadable artifacts.

Nothing in the repo called `jax.profiler` before this module; the kernel
work (ROADMAP item 1) needs to SEE device time per XLA op, and a
production replica can't be restarted under a profiler wrapper to get it.
This is the missing piece: a single-flight, duration-bounded capture you
can trigger against a LIVE ApiServer mid-job —

    POST /profile {"durationS": 3}     -> 202 {id, durationS}
    GET  /profile/{id}                 -> 202 while running,
                                          200 .tar.gz artifact when done
    dg16-cli profile capture --seconds 3 --out prof.tar.gz

The capture wraps `jax.profiler.start_trace` writing under
`DG16_PROF_DIR`; at stop the trace directory (`plugins/profile/<run>/
<host>.xplane.pb`) is tarred into one artifact, openable in TensorBoard's
profile plugin. The stop writes the xplane alone (`_stop_trace`): jax's own
`stop_trace` also converts it to a `trace.json.gz` in which every device
event repeats its whole HLO text, a Mosaic kernel's payload included, and
that second file took most of a stop's minutes under load and was read by
nothing. Captures run with the Python tracer off. While a capture is
live, `tracing.set_annotator` bridges every `tracing.span` into a
`jax.profiler.TraceAnnotation` of the same name (the `job` span's carries
its job id), so job phases (load / witness / packing / MPC Proof / dmsm / dfft...) line
up with the XLA ops they launched in ONE timeline. With no capture
running the annotator is None and the span hot path is untouched (the
PR 3 idle zero-overhead guard stays green).

Single-flight by design: `jax.profiler` is process-global state, so a
second POST while one capture runs is HTTP 409, not a queue. Durations
are clamped to `DG16_PROF_MAX_S` — a forgotten capture must not trace a
production replica for an hour.
"""

from __future__ import annotations

import os
import socket
import tarfile
import threading
import time
import uuid
from collections import OrderedDict
from dataclasses import dataclass, field

from . import metrics as _tm
from . import tracing as _tracing
from ..utils import config as _config

_REG = _tm.registry()
_CAPTURES = _REG.counter(
    "profiler_captures_total",
    "On-demand XLA profiler captures, by outcome (ok / error / rejected)",
    ("outcome",),
)
_ACTIVE = _REG.gauge(
    "profiler_active",
    "1 while an on-demand XLA capture is running (single-flight)",
)

DEFAULT_DURATION_S = 3.0
DEFAULT_MAX_S = 60.0
HISTORY = 8  # capture records kept addressable per profiler


class ProfileError(Exception):
    pass


class ProfileBusyError(ProfileError):
    """A capture is already running (single-flight; HTTP 409)."""


@dataclass
class Capture:
    """One capture's lifecycle record (the GET /profile row)."""

    id: str
    directory: str
    duration_s: float
    started_at: float = field(default_factory=time.time)
    state: str = "running"  # running | done | error
    artifact: str | None = None
    artifact_bytes: int = 0
    error: str | None = None

    def to_dict(self) -> dict:
        return {
            "id": self.id,
            "state": self.state,
            "durationS": self.duration_s,
            "startedAt": self.started_at,
            "artifactBytes": self.artifact_bytes,
            "error": self.error,
        }


# spans of the host's work outside a job's phases (PR 34): their attrs
# (`fn`, `generation`, `route`, `method`, `job`) become event stats
_STAT_SPANS = ("http", "gc", "jax.")


def _annotation_factory(name: str, attrs: dict | None):
    """The span-to-device-timeline bridge. The `job` annotation carries
    its job id as an event stat, so a trace's `job` events can be matched
    to their status DTOs; the host's spans outside the phases carry their
    attrs; every other span is a bare name (what `Span.note` adds later
    becomes stats of any)."""
    import jax

    if name == "job" and attrs and "job" in attrs:
        return jax.profiler.TraceAnnotation(name, job_id=attrs["job"])
    if attrs and name.startswith(_STAT_SPANS):
        return jax.profiler.TraceAnnotation(name, **attrs)
    return jax.profiler.TraceAnnotation(name)


def _stop_trace() -> None:
    """`jax.profiler.stop_trace()` less its `trace.json.gz`: the session's
    xplane written where jax would write it. jax keeps the session in a
    private holder; where that is not as expected (another jax, a test's
    stand-in for `start_trace`), jax's own stop runs."""
    import jax

    try:
        from jax._src.profiler import _profile_state as state

        lock, sess, log_dir = state.lock, state.profile_session, state.log_dir
    except (ImportError, AttributeError):
        sess = None
    if sess is None:
        jax.profiler.stop_trace()
        return
    with lock:
        try:
            xspace = sess.stop()
        finally:
            state.reset()  # a failed stop must not hold jax's one slot
    run_dir = os.path.join(
        log_dir, "plugins", "profile", time.strftime("%Y_%m_%d_%H_%M_%S")
    )
    os.makedirs(run_dir, exist_ok=True)
    with open(
        os.path.join(run_dir, socket.gethostname() + ".xplane.pb"), "wb"
    ) as f:
        f.write(xspace)


class Profiler:
    """Single-flight on-demand capture manager (one per process is the
    intended shape — `jax.profiler` state is global)."""

    def __init__(self, directory: str, max_s: float | None = None):
        self.directory = directory
        self.max_s = (
            max_s
            if max_s is not None
            else _config.env_float("DG16_PROF_MAX_S", DEFAULT_MAX_S)
        )
        self._lock = threading.Lock()
        self._current: Capture | None = None
        # jax.profiler is process-global: the slot must stay busy from
        # start_trace until stop_trace RETURNS, even though `_current`
        # clears at the top of stop() (so racing stops are idempotent)
        self._trace_live = False
        self._timer: threading.Timer | None = None
        self._history: OrderedDict[str, Capture] = OrderedDict()

    # -- lifecycle -----------------------------------------------------------

    def start(self, duration_s: float | None = None) -> Capture:
        """Begin a capture. `duration_s` > 0 arms a timer that stops it
        (the HTTP path — bounded by `DG16_PROF_MAX_S`); <= 0 or None means
        the CALLER stops it (`stop()`).
        Raises ProfileBusyError while another capture runs."""
        import jax

        if duration_s is not None and duration_s > 0:
            duration_s = min(float(duration_s), self.max_s)
        cap = Capture(
            id=uuid.uuid4().hex[:12],
            directory="",
            duration_s=float(duration_s or 0.0),
        )
        cap.directory = os.path.join(self.directory, cap.id)
        with self._lock:
            if self._current is not None or self._trace_live:
                _CAPTURES.labels(outcome="rejected").inc()
                raise ProfileBusyError(
                    "a capture is already running (single-flight)"
                )
            self._current = cap
            self._trace_live = True
            self._history[cap.id] = cap
            while len(self._history) > HISTORY:
                self._history.popitem(last=False)
        try:
            os.makedirs(cap.directory, exist_ok=True)
            # Python tracer off: it records every Python and C call of
            # every thread (a slice of seconds grows to hundreds of MB and
            # the host slows to a fraction of its speed). The spans reach
            # the trace as TraceAnnotations, which the host tracer keeps.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(cap.directory, profiler_options=options)
        except Exception as e:  # noqa: BLE001 — a failed start frees the slot
            with self._lock:
                self._current = None
                self._trace_live = False
            cap.state = "error"
            cap.error = f"{type(e).__name__}: {e}"
            _CAPTURES.labels(outcome="error").inc()
            raise ProfileError(cap.error) from e
        # bridge spans onto the device timeline for the capture's extent
        _tracing.set_annotator(_annotation_factory)
        _ACTIVE.set(1)
        if duration_s is not None and duration_s > 0:
            t = threading.Timer(duration_s, self.stop)
            t.daemon = True
            with self._lock:
                self._timer = t
            t.start()
        return cap

    def stop(self) -> Capture | None:
        """End the current capture, tar its trace directory into the
        downloadable artifact, and return the record (None if no capture
        was running — a late timer racing an explicit stop is benign)."""
        with self._lock:
            cap = self._current
            self._current = None
            timer, self._timer = self._timer, None
        if cap is None:
            return None
        if timer is not None:
            timer.cancel()
        _tracing.set_annotator(None)
        _ACTIVE.set(0)
        try:
            _stop_trace()
        except Exception as e:  # noqa: BLE001 — never turn profiling into a fault
            cap.state = "error"
            cap.error = f"{type(e).__name__}: {e}"
            _CAPTURES.labels(outcome="error").inc()
            return cap
        finally:
            with self._lock:
                self._trace_live = False
        try:
            cap.artifact = self._pack(cap)
            cap.artifact_bytes = os.path.getsize(cap.artifact)
            cap.state = "done"
            _CAPTURES.labels(outcome="ok").inc()
        except Exception as e:  # noqa: BLE001 — tarfile raises TarError too;
            # an escaped exception here (timer thread) would strand the
            # capture in "running" and make the CLI poll until timeout
            cap.state = "error"
            cap.error = f"{type(e).__name__}: {e}"
            _CAPTURES.labels(outcome="error").inc()
        return cap

    def _pack(self, cap: Capture) -> str:
        """Tar the trace directory (the xplane.pb) into
        `<id>.tar.gz` next to it — one downloadable file per capture."""
        path = os.path.join(self.directory, f"{cap.id}.tar.gz")
        with tarfile.open(path, "w:gz") as tar:
            tar.add(cap.directory, arcname=cap.id)
        return path

    # -- the read side -------------------------------------------------------

    def get(self, capture_id: str) -> Capture | None:
        with self._lock:
            return self._history.get(capture_id)

    def active(self) -> Capture | None:
        with self._lock:
            return self._current

    def stats(self) -> dict:
        with self._lock:
            caps = list(self._history.values())
            current = self._current
        return {
            "directory": self.directory,
            "maxDurationS": self.max_s,
            "running": current.id if current is not None else None,
            "captures": [c.to_dict() for c in caps],
        }

