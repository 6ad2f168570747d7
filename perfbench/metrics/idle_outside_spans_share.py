"""Device-idle time while no span of a group is open on the loop's thread, as a share of the traced whole
cycles: the guard that the loop's spans tile its iteration."""

from perfbench.harness.host_idle import share


def read(run):
    return share(run, "outside")
