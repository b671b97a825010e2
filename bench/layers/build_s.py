"""Build (`hnsw.bulk_build`): host clock around `LSMVecIndex.build` and
a device sync, compiling included."""


def read(run):
    return run.setup["build_s"]
