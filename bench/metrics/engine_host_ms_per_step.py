"""Host milliseconds per window step inside ``Engine.step`` outside
``engine.fetch``: the step's host work while the device has no step of its
own queued (``engine.fetch`` is the wait for the sampled rows, inside which
the device step ends). Reads the engine's own span counters
(``Engine.span_s`` / ``span_n``) taken when the window opens and closes,
as ``run.span_s`` / ``run.span_n`` (their changes over the window); None
where the run carries none."""


def read(run):
    s, n = getattr(run, "span_s", None), getattr(run, "span_n", None)
    steps = (n or {}).get("engine.step", 0)
    if not s or not steps:
        return None
    return 1e3 * (s["engine.step"] - s.get("engine.fetch", 0.0)) / steps
