"""Most that share can have been: engine_device_dry_seconds_total
{bound="ceiling"} counts every interval between two probes that did not end on
a probe finding the device busy. The truth lies between the floor and this;
their gap is the probes' spacing."""
from chipbench.layer_metrics._sched import dry_share


def read(ctx):
    return dry_share(ctx, "ceiling")
