"""events_per_s: events sent and delivered to the output callback over
the whole timed window (host clock). The window closes at the first
chunk boundary after --seconds, so this is all work over all time."""


def read(run):
    if not run.window_s:
        return None
    return run.events_window / run.window_s
