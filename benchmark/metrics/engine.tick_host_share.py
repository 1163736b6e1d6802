"""Share of the engine's steps spent outside a compiled program, in
percent: 100 x (sum of ``serve.step`` - sum of ``serve.program``) over the
sum of ``serve.step``, over the window.  ``serve.program`` ends when the
logits are ready, so this is the part of a tick in which the chip waits
for the host."""
from benchmark import spanlog


def read(ctx):
    spans = spanlog.window_spans(ctx)
    if not spans.get("serve.step") or not spans.get("serve.program"):
        return None
    step = sum(e["dur"] for e in spans["serve.step"])
    program = sum(e["dur"] for e in spans["serve.program"])
    return 100.0 * (step - program) / step if step else None
