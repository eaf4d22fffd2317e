"""setup_s: seconds from process start to the window's start: imports,
device start-up, building the inputs, and warming (compiling or loading)
every program the window uses."""


def read(record):
    return record["setup_s"]
