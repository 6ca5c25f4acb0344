"""device step: device time, per send in the traced slice, of the pattern
program executions whose `[Kb, E]` rectangle has the largest E of those
that ran — a tiered send's hot tier (`rect_64x2048` in
`pattern_16m_zipf.paced`), which is dispatched first and is the send's
critical path; in a cell whose sends are one rectangle, the pattern
program's whole time (`device_busy` less the other modules).  The rectangle
is the program's outermost scope in each device op's `tf_op`
(harness/step_sections.py); None on a program without it."""
from benchmarks.harness.step_sections import hot_rect_ms_per_send


def read(run):
    return hot_rect_ms_per_send(run)
