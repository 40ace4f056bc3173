"""``PERFBENCH_REHEARSAL=1``: the same control flow at tiny widths on the CPU.

For the benchmark's own tests and for rehearsing a change before a chip
call. Its numbers mean nothing and its line says ``"platform": "cpu"``. What
shrinks is said by the files themselves: the model module's ``REHEARSAL``
replaces the configuration's sizes, the cell runner's ``REHEARSAL`` its
deployment section, and a traffic file's own ``rehearsal`` object its
parameters.
"""

from __future__ import annotations

import copy


def shrink(config: dict, traffic: dict, model, runner):
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config.update(model.REHEARSAL)
    for section, small in getattr(runner, "REHEARSAL", {}).items():
        if section in config:
            config[section].update(small)
    traffic.update(traffic.get("rehearsal", {}))
    return config, traffic
