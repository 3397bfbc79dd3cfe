"""Trees the window left unused: the workload's `build_trees` less the
warm-up's one and one for each build the window sent.  0 says that
the client's loop ended for want of trees before the window did, so
the run holds less work than its seconds would."""

META = {'layer': 'serve', 'source': 'host_clock', 'unit': 'count', 'better': 'higher',
        'moves': 'build_records_per_s'}


def read(r):
    sent = [o for o in r.outcomes if o.req.template['op'] == 'build']
    return float(r.workload['build_trees'] - 1 - len(sent))
