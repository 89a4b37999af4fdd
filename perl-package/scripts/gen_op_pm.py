#!/usr/bin/env python
"""Generate perl-package/lib/AI/MXTpu/Ops.pm from the op registry.

Analog of the reference's runtime op autogeneration in
perl-package/AI-MXNet/lib/AI/MXNet/NDArray.pm (_init_ns walking
MXListAllOpNames) and of cpp-package/scripts/gen_op_h.py here: one
named Perl sub per registered operator, funneling through
AI::MXTpu::op (imperative invoke over the C ABI). The generated file
is checked in, like the C++ op.h. Regenerate after adding ops:

    PYTHONPATH=. python perl-package/scripts/gen_op_pm.py
"""
import inspect
import keyword
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

# subs that would collide with Perl builtins/keywords get a trailing _
PERL_RESERVED = {
    "abs", "exp", "log", "sqrt", "sin", "cos", "sort", "reverse", "split",
    "join", "keys", "values", "each", "push", "pop", "shift", "unshift",
    "splice", "map", "grep", "print", "printf", "sprintf", "pack",
    "unpack", "length", "substr", "index", "rindex", "ord", "chr", "uc",
    "lc", "crypt", "eval", "exec", "sleep", "time", "localtime", "gmtime",
    "die", "warn", "ref", "bless", "tie", "untie", "local", "my", "our",
    "sub", "do", "if", "else", "elsif", "unless", "while", "until", "for",
    "foreach", "last", "next", "redo", "return", "and", "or", "not", "xor",
    "lt", "gt", "le", "ge", "eq", "ne", "cmp", "x", "q", "qq", "qw", "qr",
    "tr", "y", "s", "m", "no", "use", "package", "require", "wantarray",
    "defined", "delete", "exists", "scalar", "undef", "chomp", "chop",
    "lcfirst", "ucfirst", "int", "hex", "oct", "rand", "srand", "sum",
    "max", "min", "open", "close", "read", "write", "seek", "tell", "stat",
    "flip", "dot", "sign",
}

HEADER = '''\
package AI::MXTpu::Ops;

# GENERATED FILE - do not edit; run perl-package/scripts/gen_op_pm.py.
#
# One sub per operator in the live registry (%(count)d ops), each a
# thin funnel into AI::MXTpu::op("<name>", @inputs, %%params) - the
# imperative-invoke path of the C ABI. Names shadowing Perl builtins
# carry a trailing underscore (relu is relu, but abs is abs_).
#
# ref: perl-package/AI-MXNet/lib/AI/MXNet/NDArray.pm autogenerates the
# same surface at runtime from MXListAllOpNames.

use strict;
use warnings;

use AI::MXTpu;

'''

FOOTER = '''\
1;
'''


def perl_name(name):
    if not name.isidentifier() or keyword.iskeyword(name):
        return None
    if name.startswith("_"):
        return None
    return name + "_" if name.lower() in PERL_RESERVED else name


def main(out_path=None):
    from mxnet_tpu.ops import registry

    body = []
    emitted = set()
    for name in sorted(registry.list_ops()):
        pname = perl_name(name)
        if pname is None or pname in emitted:
            continue
        emitted.add(pname)
        fn = registry.get_op(name).fn
        # the signature of this package's own functions only: one that is
        # jax.numpy's is JAX's to reword, and the file would go stale with
        # a JAX release
        sig = "(...)"
        if (getattr(fn, "__module__", None) or "").startswith("mxnet_tpu"):
            try:
                sig = str(inspect.signature(fn))
            except (TypeError, ValueError):
                pass
        body.append("# %s%s\n" % (name, sig))
        body.append("sub %s { AI::MXTpu::op('%s', @_) }\n\n"
                    % (pname, name))

    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "lib", "AI", "MXTpu", "Ops.pm")
    with open(out_path, "w") as f:
        f.write(HEADER % {"count": len(emitted)})
        f.writelines(body)
        f.write(FOOTER)
    print("wrote %s (%d ops)" % (os.path.normpath(out_path), len(emitted)))


if __name__ == "__main__":
    # optional explicit output path (CI generates to a temp file and
    # diffs against the checked-in copy without touching the tree)
    main(sys.argv[1] if len(sys.argv) > 1 else None)
