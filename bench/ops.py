"""The switch opcodes and key encoding, as the program's packet format
and txn model define them.  The benchmark keeps its own copy so that the
traffic generators and the reference import nothing of the program;
``tests/test_bench_traffic.py`` pins the copy against the program."""

NOP, READ, WRITE, ADD, CADD, ADDP = 0, 1, 2, 3, 4, 5

# a global key is node * NODE_STRIDE + local (one table, partitioned)
NODE_STRIDE = 1_000_000_000


def key_of(node, local):
    return node * NODE_STRIDE + local
