"""Share of the traced window in which the busiest chip ran no op and
the calling thread had no ``phase:*`` annotation open: idle time that no
program layer names (device trace and program spans, one clock)."""

from sortbench import progspans


def read(run):
    return progspans.idle_outside_pct(run)
