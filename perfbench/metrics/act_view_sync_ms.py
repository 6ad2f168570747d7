"""A train call's wait for its act view: from the end of the call's last `train_step` execution on the device
to the end of the host's `act_view.fetch` span (pack on the device, wait, copy to the host), on the capture's clock."""

from perfbench.harness import program_spans
from perfbench.harness.program_spans import from_capture


def read(run):
    return from_capture(run, program_spans.act_view_sync_ms)
