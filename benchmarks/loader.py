"""Find a benchmark file by the name a data file gives."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(kind, name):
    """benchmarks/<kind>/<name>.py as a module.  (By path, not by
    import: a metric's file name holds dots, and the directory `trace`
    may not shadow the standard library's module.)"""
    path = os.path.join(HERE, kind, name + '.py')
    spec = importlib.util.spec_from_file_location(
        'bench_%s_%s' % (kind, name.replace('.', '_').replace('-', '_')),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
