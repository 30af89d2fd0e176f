"""Host seconds of lowering and compiling the planned step
(``ShardingPlan.apply`` and XLA), timed by the harness."""


def read(record):
    return record["compile_s"]
