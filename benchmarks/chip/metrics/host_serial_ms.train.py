"""Host time per train step in which the trainer's loop holds the chip
idle: the program's ``repro.train.input`` (batch fetch and upload),
``repro.train.launch`` (guard scalars, the step's dispatch),
``repro.train.readback`` (the step counter) and ``repro.train.callbacks``
spans inside the traced window, summed over the window's steps and
divided by them. ``repro.train.wait``, where the host waits on the device,
is left out. The loop syncs on every step, so the device has no work
queued while the host is in these spans. Nothing where the program writes
no such span. Each span's own ms per step, ``wait`` too, is printed to
standard error."""
import sys

SPANS = ("repro.train.input", "repro.train.launch", "repro.train.readback",
         "repro.train.callbacks")


def read(ctx):
    if ctx["kind"] != "train" or not ctx["steps"]:
        return None
    lo, hi = ctx["lo"], ctx["hi"]
    ms = {}
    for s in ctx["trace"].spans:
        if s.name.startswith("repro.train.") and s.start_ns < hi and s.end_ns > lo:
            part = (min(s.end_ns, hi) - max(s.start_ns, lo)) * 1e-6 / ctx["steps"]
            ms[s.name] = ms.get(s.name, 0.0) + part
    if not any(name in ms for name in SPANS):
        return None
    print("host_serial: ms per step " + ", ".join(f"{k} {v!r}" for k, v in ms.items()),
          file=sys.stderr)
    return sum(ms.get(name, 0.0) for name in SPANS)
