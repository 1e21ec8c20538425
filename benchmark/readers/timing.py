"""A host-clock duration the runner stamped: args {"key": "setup_s"}."""


def read(data, args):
    return data["timing"].get(args["key"])
