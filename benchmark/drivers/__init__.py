"""Traffic drivers, one module per kind, found by name.

A traffic mix is a data file `benchmark/traffic/<name>.json` whose
`"driver"` names a module here.  A driver is a class `Driver(params, run)`
with the methods below; `run` is the `run.Run` of this process.

    prepare()            before the server starts: build its volumes
    warm(cluster)        server up: untimed warm-up of the cell's shapes
    run(cluster, seconds)  the measured window
    check_live(compared)   window closed, server alive: reads over HTTP
    check_files(compared)  server gone: files against the reference
"""

from __future__ import annotations

import importlib


def make(spec: dict, run):
    mod = importlib.import_module(f"{__name__}.{spec['driver']}")
    return mod.Driver(spec, run)
