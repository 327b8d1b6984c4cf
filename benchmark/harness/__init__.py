"""The benchmark's harness: everything general. What belongs to one
configuration, dataset, query family, traffic mix or metric is a file in
the directory of that name beside this one."""
