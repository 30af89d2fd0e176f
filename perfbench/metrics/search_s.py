"""Host seconds of ``Session.partition``: cost model, action space and
search (``ShardingPlan.search_seconds``)."""


def read(record):
    return record["search_seconds"]
