"""refresh: ``refresh_extra_ms`` in a cell whose refresh inverts expert banks
(``ops/precondition.py::factored_inverse_tables``): median device time of the
traced ``refresh`` steps minus that of the ``factors`` steps they replace.
The same quantity as ``refresh_extra_ms.tail`` (refresh steps are a tenth of
this cell's steps too, so they are its tail); it has a name and a file of its
own only because the accepted reader's ``MOVES`` lists its two names and a
``model_config`` PR may not edit it. The next ``benchmark`` PR merges them."""
import statistics

LAYER = "refresh"
MOVES = "step_p95_ms"


def read(run):
    ms = run["device_ms"]
    if "refresh" not in ms or "factors" not in ms:
        return None
    return statistics.median(ms["refresh"]) - statistics.median(ms["factors"])
