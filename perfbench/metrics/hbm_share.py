"""Share of a device's memory the compiled step needs, from XLA's
static count (``memory_analysis()``: arguments + temporaries + outputs
- aliased) over the device's ``bytes_limit``."""


def read(record):
    if not record["bytes_limit"]:
        return None
    return 100.0 * record["memory"]["step_bytes"] / record["bytes_limit"]
