"""Host milliseconds a step spends inside the loop's `bench.dispatch` spans
(feed conversion and `Executor.run` up to the asynchronous launch), from the
traced stretch."""


def read(ctx):
    spans = [dur for name, _, dur in ctx["trace"].host
             if name == "bench.dispatch"]
    if not spans:
        return None
    return sum(spans) * 1e-6 / ctx["trace"].steps
