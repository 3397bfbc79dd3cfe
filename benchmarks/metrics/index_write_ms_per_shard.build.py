"""Host milliseconds of index write per daily shard:
`stage_ms{index_build.prepare}` + `{index_build.commit}` over the
window / shards written."""

import readers

META = {'layer': 'index write', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'build_records_per_s'}


def read(r):
    ms = readers.stage_ms(r, 'index_build.prepare', 'index_build.commit')
    shards = r.config['corpus']['days'] * len(r.done('build'))
    return ms / shards if ms is not None and shards else None
