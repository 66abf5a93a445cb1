"""Reference-artifact importer (VERDICT r3 item 7).

Authors a genuine reference-format artifact — `__model__` ProgramDesc
protobuf (framework.proto:50-240) + combined persistables in the
SerializeToStream layout (lod_tensor.cc:190) — with an independent encoder,
then imports and executes it, checking numerics against numpy.
"""
import struct

import numpy as np
import pytest

from paddle_tpu.interop import load_paddle_inference_model
from paddle_tpu.interop.wire import (
    enc_bytes, enc_f32, enc_int, enc_tag, enc_varint, LEN,
)

FP32 = 5
LOD_TENSOR = 7
FEED_MINIBATCH = 9
FETCH_LIST = 10
(A_INT, A_FLOAT, A_STRING, A_INTS, A_FLOATS, A_STRINGS, A_BOOL,
 A_BOOLS) = range(8)


def msg(fno, payload):
    return enc_tag(fno, LEN) + enc_varint(len(payload)) + payload


def tensor_desc(dtype, dims):
    return enc_int(1, dtype) + b"".join(enc_int(2, d) for d in dims)


def var_desc(name, dtype=FP32, dims=(), persistable=False,
             type_id=LOD_TENSOR):
    vt = enc_int(1, type_id)
    if type_id == LOD_TENSOR:
        vt += msg(3, msg(1, tensor_desc(dtype, dims)))
    out = enc_bytes(1, name) + msg(2, vt)
    if persistable:
        out += enc_int(3, 1)
    return out


def attr(name, atype, value):
    out = enc_bytes(1, name) + enc_int(2, atype)
    if atype == A_INT:
        out += enc_int(3, value)
    elif atype == A_FLOAT:
        out += enc_f32(4, value)
    elif atype == A_STRING:
        out += enc_bytes(5, value)
    elif atype == A_INTS:
        out += b"".join(enc_int(6, v) for v in value)
    elif atype == A_BOOL:
        out += enc_int(10, int(value))
    return out


def op_desc(op_type, inputs, outputs, attrs=()):
    out = b""
    for param, args in inputs:
        out += msg(1, enc_bytes(1, param)
                   + b"".join(enc_bytes(2, a) for a in args))
    for param, args in outputs:
        out += msg(2, enc_bytes(1, param)
                   + b"".join(enc_bytes(2, a) for a in args))
    out += enc_bytes(3, op_type)
    for a in attrs:
        out += msg(4, a)
    return out


def block_desc(idx, vars_, ops):
    out = enc_int(1, idx) + enc_int(2, -1 if idx == 0 else 0)
    out += b"".join(msg(3, v) for v in vars_)
    out += b"".join(msg(4, o) for o in ops)
    return out


def program_desc(blocks):
    return b"".join(msg(1, b) for b in blocks)


def lod_tensor_stream(arr):
    """SerializeToStream: u32 ver, u64 lod_level(0), u32 ver, i32 desc size,
    TensorDesc, raw data."""
    desc = tensor_desc(FP32, arr.shape)
    return (struct.pack("<I", 0) + struct.pack("<Q", 0)
            + struct.pack("<I", 0) + struct.pack("<i", len(desc))
            + desc + np.ascontiguousarray(arr, np.float32).tobytes())


@pytest.fixture
def mlp_artifact(tmp_path):
    """feed -> mul(w1) -> +b1 -> relu -> mul(w2) -> +b2 -> softmax -> fetch"""
    rs = np.random.RandomState(0)
    w1 = rs.randn(4, 8).astype(np.float32)
    b1 = rs.randn(8).astype(np.float32)
    w2 = rs.randn(8, 3).astype(np.float32)
    b2 = rs.randn(3).astype(np.float32)

    vars_ = [
        var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
        var_desc("fetch", type_id=FETCH_LIST, persistable=True),
        var_desc("x", dims=(-1, 4)),
        var_desc("w1", dims=(4, 8), persistable=True),
        var_desc("b1", dims=(8,), persistable=True),
        var_desc("w2", dims=(8, 3), persistable=True),
        var_desc("b2", dims=(3,), persistable=True),
        var_desc("h0", dims=(-1, 8)), var_desc("h1", dims=(-1, 8)),
        var_desc("h2", dims=(-1, 8)), var_desc("h3", dims=(-1, 3)),
        var_desc("h4", dims=(-1, 3)), var_desc("out", dims=(-1, 3)),
    ]
    ops = [
        op_desc("feed", [("X", ["feed"])], [("Out", ["x"])],
                [attr("col", A_INT, 0)]),
        op_desc("mul", [("X", ["x"]), ("Y", ["w1"])], [("Out", ["h0"])],
                [attr("x_num_col_dims", A_INT, 1),
                 attr("y_num_col_dims", A_INT, 1)]),
        op_desc("elementwise_add", [("X", ["h0"]), ("Y", ["b1"])],
                [("Out", ["h1"])], [attr("axis", A_INT, -1)]),
        op_desc("relu", [("X", ["h1"])], [("Out", ["h2"])]),
        op_desc("mul", [("X", ["h2"]), ("Y", ["w2"])], [("Out", ["h3"])],
                [attr("x_num_col_dims", A_INT, 1),
                 attr("y_num_col_dims", A_INT, 1)]),
        op_desc("elementwise_add", [("X", ["h3"]), ("Y", ["b2"])],
                [("Out", ["h4"])], [attr("axis", A_INT, -1)]),
        op_desc("softmax", [("X", ["h4"])], [("Out", ["out"])],
                [attr("axis", A_INT, -1)]),
        op_desc("fetch", [("X", ["out"])], [("Out", ["fetch"])],
                [attr("col", A_INT, 0)]),
    ]
    prog = program_desc([block_desc(0, vars_, ops)])
    (tmp_path / "__model__").write_bytes(prog)
    # combined persistables, sorted by name: b1, b2, w1, w2
    with open(tmp_path / "__params__", "wb") as f:
        for arr in (b1, b2, w1, w2):
            f.write(lod_tensor_stream(arr))
    weights = dict(w1=w1, b1=b1, w2=w2, b2=b2)
    return tmp_path, weights


def _np_mlp(x, w):
    h = np.maximum(x @ w["w1"] + w["b1"], 0.0)
    z = h @ w["w2"] + w["b2"]
    e = np.exp(z - z.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def test_import_and_run_matches_numpy(mlp_artifact):
    path, w = mlp_artifact
    prog = load_paddle_inference_model(str(path),
                                       params_filename="__params__")
    assert prog.feed_names == ["x"]
    assert prog.fetch_names == ["out"]
    x = np.random.RandomState(1).randn(5, 4).astype(np.float32)
    (got,) = prog.run({"x": x})
    np.testing.assert_allclose(got, _np_mlp(x, w), rtol=1e-5, atol=1e-6)


def test_imported_model_compiles_under_jit(mlp_artifact):
    import jax

    path, w = mlp_artifact
    prog = load_paddle_inference_model(str(path),
                                       params_filename="__params__")
    fn = jax.jit(lambda feed: prog.as_fn()(feed))
    x = np.random.RandomState(2).randn(3, 4).astype(np.float32)
    (got,) = fn({"x": x})
    np.testing.assert_allclose(np.asarray(got), _np_mlp(x, w),
                               rtol=1e-5, atol=1e-6)


def test_separate_param_files(tmp_path, mlp_artifact):
    src, w = mlp_artifact
    # re-lay the same program with one file per var (save_params layout)
    (tmp_path / "__model__").write_bytes((src / "__model__").read_bytes())
    for name, arr in w.items():
        (tmp_path / name).write_bytes(lod_tensor_stream(arr))
    prog = load_paddle_inference_model(str(tmp_path))
    x = np.random.RandomState(3).randn(2, 4).astype(np.float32)
    (got,) = prog.run({"x": x})
    np.testing.assert_allclose(got, _np_mlp(x, w), rtol=1e-5, atol=1e-6)


def test_conv_pool_bn_model(tmp_path):
    """conv2d -> batch_norm (inference) -> relu -> pool2d -> flatten."""
    rs = np.random.RandomState(4)
    kernel = rs.randn(6, 3, 3, 3).astype(np.float32)
    scale = rs.rand(6).astype(np.float32) + 0.5
    bias = rs.randn(6).astype(np.float32)
    mean = rs.randn(6).astype(np.float32) * 0.1
    var = rs.rand(6).astype(np.float32) + 0.5

    vars_ = [
        var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
        var_desc("fetch", type_id=FETCH_LIST, persistable=True),
        var_desc("img", dims=(-1, 3, 8, 8)),
        var_desc("k", dims=(6, 3, 3, 3), persistable=True),
        var_desc("bn_s", dims=(6,), persistable=True),
        var_desc("bn_b", dims=(6,), persistable=True),
        var_desc("bn_m", dims=(6,), persistable=True),
        var_desc("bn_v", dims=(6,), persistable=True),
        var_desc("c0", dims=(-1, 6, 8, 8)), var_desc("c1", dims=(-1, 6, 8, 8)),
        var_desc("c2", dims=(-1, 6, 8, 8)), var_desc("p0", dims=(-1, 6, 4, 4)),
        var_desc("out", dims=(-1, 96)),
    ]
    ops = [
        op_desc("feed", [("X", ["feed"])], [("Out", ["img"])],
                [attr("col", A_INT, 0)]),
        op_desc("conv2d", [("Input", ["img"]), ("Filter", ["k"])],
                [("Output", ["c0"])],
                [attr("strides", A_INTS, [1, 1]),
                 attr("paddings", A_INTS, [1, 1]),
                 attr("dilations", A_INTS, [1, 1]),
                 attr("groups", A_INT, 1)]),
        op_desc("batch_norm",
                [("X", ["c0"]), ("Scale", ["bn_s"]), ("Bias", ["bn_b"]),
                 ("Mean", ["bn_m"]), ("Variance", ["bn_v"])],
                [("Y", ["c1"])], [attr("epsilon", A_FLOAT, 1e-5)]),
        op_desc("relu", [("X", ["c1"])], [("Out", ["c2"])]),
        op_desc("pool2d", [("X", ["c2"])], [("Out", ["p0"])],
                [attr("pooling_type", A_STRING, "max"),
                 attr("ksize", A_INTS, [2, 2]),
                 attr("strides", A_INTS, [2, 2]),
                 attr("paddings", A_INTS, [0, 0])]),
        op_desc("flatten_contiguous_range", [("X", ["p0"])],
                [("Out", ["out"])],
                [attr("start_axis", A_INT, 1), attr("stop_axis", A_INT, 3)]),
        op_desc("fetch", [("X", ["out"])], [("Out", ["fetch"])],
                [attr("col", A_INT, 0)]),
    ]
    (tmp_path / "__model__").write_bytes(
        program_desc([block_desc(0, vars_, ops)]))
    with open(tmp_path / "__params__", "wb") as f:
        # sorted: bn_b, bn_m, bn_s, bn_v, k
        for arr in (bias, mean, scale, var, kernel):
            f.write(lod_tensor_stream(arr))

    prog = load_paddle_inference_model(str(tmp_path),
                                       params_filename="__params__")
    x = rs.randn(2, 3, 8, 8).astype(np.float32)
    (got,) = prog.run({"img": x})

    # numpy oracle
    import jax

    conv = np.asarray(jax.lax.conv_general_dilated(
        x, kernel, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NCHW", "OIHW", "NCHW")))
    sh = (1, 6, 1, 1)
    bn = ((conv - mean.reshape(sh)) / np.sqrt(var.reshape(sh) + 1e-5)
          * scale.reshape(sh) + bias.reshape(sh))
    r = np.maximum(bn, 0)
    pooled = r.reshape(2, 6, 4, 2, 4, 2).max((3, 5))
    np.testing.assert_allclose(got, pooled.reshape(2, -1),
                               rtol=1e-4, atol=1e-5)


def test_unmapped_op_raises_with_name(tmp_path):
    vars_ = [var_desc("x", dims=(2,)), var_desc("y", dims=(2,))]
    ops = [op_desc("some_exotic_op", [("X", ["x"])], [("Out", ["y"])])]
    (tmp_path / "__model__").write_bytes(
        program_desc([block_desc(0, vars_, ops)]))
    prog = load_paddle_inference_model(str(tmp_path))
    with pytest.raises(NotImplementedError, match="some_exotic_op"):
        prog.run({"x": np.zeros(2, np.float32)})


def test_create_predictor_serves_reference_artifact(mlp_artifact):
    """The standard inference API (Config -> create_predictor -> handles)
    must serve reference-format models directly — the ecosystem-migration
    path: point the predictor at a saved reference model dir."""
    from paddle_tpu.inference import Config, create_predictor

    path, w = mlp_artifact
    cfg = Config(str(path))
    pred = create_predictor(cfg)
    assert pred.get_input_names() == ["x"]
    x = np.random.RandomState(5).randn(4, 4).astype(np.float32)
    h = pred.get_input_handle("x")
    h.copy_from_cpu(x)
    pred.run()
    out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(out, _np_mlp(x, w), rtol=1e-5, atol=1e-6)


def test_save_optimized_model_roundtrip(tmp_path, mlp_artifact):
    """AnalysisPredictor::SaveOptimModel (analysis_predictor.h:265): a
    predictor serving a reference __model__ dir persists the optimized
    model as the NATIVE artifact triple; a fresh predictor on that prefix
    serves identical outputs without touching the reference format."""
    from paddle_tpu.inference import Config, create_predictor

    path, w = mlp_artifact
    pred = create_predictor(Config(str(path)))
    x = np.random.RandomState(7).randn(4, 4).astype(np.float32)
    (ref_out,) = pred.run([x])

    prefix = str(tmp_path / "optim" / "mlp")
    pdmodel = pred.save_optimized_model(prefix)
    assert pdmodel.endswith(".pdmodel")
    import os
    for suffix in (".pdmodel", ".pdiparams", ".manifest.json"):
        assert os.path.exists(prefix + suffix), suffix

    pred2 = create_predictor(Config(prefix))
    from paddle_tpu.inference.io import InferenceArtifact
    assert isinstance(pred2._artifact, InferenceArtifact)  # native load
    (out2,) = pred2.run([x])
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref_out),
                               rtol=1e-6, atol=1e-7)
    # the dynamic batch dim survives export: another batch size serves
    x8 = np.random.RandomState(8).randn(8, 4).astype(np.float32)
    (out8,) = pred2.run([x8])
    np.testing.assert_allclose(np.asarray(out8), _np_mlp(x8, w),
                               rtol=1e-5, atol=1e-6)

    # native artifacts re-save as-is
    prefix3 = str(tmp_path / "resave" / "mlp")
    pred2.save_optimized_model(prefix3)
    pred3 = create_predictor(Config(prefix3))
    (out3,) = pred3.run([x])
    np.testing.assert_allclose(np.asarray(out3), np.asarray(ref_out),
                               rtol=1e-6, atol=1e-7)


def test_create_predictor_pdmodel_protobuf(tmp_path, mlp_artifact):
    """prefix.pdmodel holding a reference ProgramDesc (not our StableHLO
    blob, no manifest) + prefix.pdiparams combined persistables."""
    from paddle_tpu.inference import Config, create_predictor

    src, w = mlp_artifact
    (tmp_path / "m.pdmodel").write_bytes((src / "__model__").read_bytes())
    (tmp_path / "m.pdiparams").write_bytes((src / "__params__").read_bytes())
    pred = create_predictor(Config(str(tmp_path / "m.pdmodel")))
    x = np.random.RandomState(6).randn(2, 4).astype(np.float32)
    (out,) = pred.run([x])
    np.testing.assert_allclose(np.asarray(out.copy_to_cpu()
                                          if hasattr(out, "copy_to_cpu")
                                          else out),
                               _np_mlp(x, w), rtol=1e-5, atol=1e-6)


def test_predictor_explicit_params_file(tmp_path, mlp_artifact):
    """Config(model, params) two-file signature with a non-prefix params
    name must load the named params file."""
    from paddle_tpu.inference import Config, create_predictor

    src, w = mlp_artifact
    (tmp_path / "net.pdmodel").write_bytes((src / "__model__").read_bytes())
    (tmp_path / "weights.bin").write_bytes((src / "__params__").read_bytes())
    pred = create_predictor(Config(str(tmp_path / "net.pdmodel"),
                                   str(tmp_path / "weights.bin")))
    x = np.random.RandomState(7).randn(2, 4).astype(np.float32)
    h = pred.get_input_handle("x")
    h.copy_from_cpu(x)
    pred.run()
    out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(out, _np_mlp(x, w), rtol=1e-5, atol=1e-6)


def test_pdmodel_missing_params_fails_at_load(tmp_path, mlp_artifact):
    from paddle_tpu.inference import Config, create_predictor

    src, _ = mlp_artifact
    (tmp_path / "net.pdmodel").write_bytes((src / "__model__").read_bytes())
    with pytest.raises(FileNotFoundError):
        create_predictor(Config(str(tmp_path / "net.pdmodel")))


def test_mobile_ops_numerics(tmp_path):
    """The mobile-net op tail: depthwise conv, hard_swish, leaky_relu,
    adaptive pool, interp, gather/stack/arg_max — numerics vs numpy/jax."""
    import jax

    rs = np.random.RandomState(8)
    dw = rs.randn(3, 1, 3, 3).astype(np.float32)  # depthwise [C,1,kh,kw]
    vars_ = [
        var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
        var_desc("fetch", type_id=FETCH_LIST, persistable=True),
        var_desc("img", dims=(-1, 3, 8, 8)),
        var_desc("dw", dims=(3, 1, 3, 3), persistable=True),
        var_desc("c0", dims=(-1, 3, 8, 8)), var_desc("h0", dims=(-1, 3, 8, 8)),
        var_desc("h1", dims=(-1, 3, 8, 8)), var_desc("p0", dims=(-1, 3, 2, 2)),
        var_desc("u0", dims=(-1, 3, 4, 4)), var_desc("am", dims=(-1, 3, 4)),
    ]
    ops = [
        op_desc("feed", [("X", ["feed"])], [("Out", ["img"])],
                [attr("col", A_INT, 0)]),
        op_desc("depthwise_conv2d", [("Input", ["img"]), ("Filter", ["dw"])],
                [("Output", ["c0"])],
                [attr("strides", A_INTS, [1, 1]),
                 attr("paddings", A_INTS, [1, 1]),
                 attr("dilations", A_INTS, [1, 1]),
                 attr("groups", A_INT, 3)]),
        op_desc("hard_swish", [("X", ["c0"])], [("Out", ["h0"])]),
        op_desc("leaky_relu", [("X", ["h0"])], [("Out", ["h1"])],
                [attr("alpha", A_FLOAT, 0.1)]),
        op_desc("pool2d", [("X", ["h1"])], [("Out", ["p0"])],
                [attr("pooling_type", A_STRING, "avg"),
                 attr("ksize", A_INTS, [2, 2]),
                 attr("adaptive", A_BOOL, True)]),
        op_desc("nearest_interp_v2", [("X", ["p0"])], [("Out", ["u0"])],
                [attr("out_h", A_INT, 4), attr("out_w", A_INT, 4)]),
        op_desc("arg_max", [("X", ["u0"])], [("Out", ["am"])],
                [attr("axis", A_INT, -1)]),
        op_desc("fetch", [("X", ["am"])], [("Out", ["fetch"])],
                [attr("col", A_INT, 0)]),
    ]
    (tmp_path / "__model__").write_bytes(
        program_desc([block_desc(0, vars_, ops)]))
    with open(tmp_path / "__params__", "wb") as f:
        f.write(lod_tensor_stream(dw))

    prog = load_paddle_inference_model(str(tmp_path),
                                       params_filename="__params__")
    x = rs.randn(2, 3, 8, 8).astype(np.float32)
    (got,) = prog.run({"img": x})

    conv = np.asarray(jax.lax.conv_general_dilated(
        x, dw, (1, 1), [(1, 1), (1, 1)], feature_group_count=3,
        dimension_numbers=("NCHW", "OIHW", "NCHW")))
    hs = conv * np.clip(conv + 3.0, 0, 6.0) / 6.0
    lr = np.where(hs >= 0, hs, 0.1 * hs)
    pooled = lr.reshape(2, 3, 2, 4, 2, 4).mean((3, 5))
    up = pooled.repeat(2, axis=2).repeat(2, axis=3)
    ref = up.argmax(-1)
    np.testing.assert_array_equal(got, ref)


A_BLOCK = 8
INT32 = 2
BOOL = 0


def attr_block(name, block_idx):
    return (enc_bytes(1, name) + enc_int(2, A_BLOCK)
            + enc_int(12, block_idx))


def test_imported_while_loop(tmp_path):
    """A reference-style while program: acc/i live in the enclosing scope;
    the sub-block increments, accumulates and recomputes Condition —
    trip count follows the FED bound."""
    vars_main = [
        var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
        var_desc("fetch", type_id=FETCH_LIST, persistable=True),
        var_desc("n", dtype=FP32, dims=()),
        var_desc("i", dtype=FP32, dims=()),
        var_desc("acc", dtype=FP32, dims=()),
        var_desc("cond", dtype=BOOL, dims=()),
    ]
    ops_main = [
        op_desc("feed", [("X", ["feed"])], [("Out", ["n"])],
                [attr("col", A_INT, 0)]),
        op_desc("fill_constant", [], [("Out", ["i"])],
                [attr("shape", A_INTS, []), attr("value", A_FLOAT, 0.0),
                 attr("dtype", A_INT, FP32)]),
        op_desc("fill_constant", [], [("Out", ["acc"])],
                [attr("shape", A_INTS, []), attr("value", A_FLOAT, 0.0),
                 attr("dtype", A_INT, FP32)]),
        op_desc("less_than", [("X", ["i"]), ("Y", ["n"])],
                [("Out", ["cond"])]),
        op_desc("while",
                [("X", ["i", "acc", "n"]), ("Condition", ["cond"])],
                [("Out", ["i", "acc"])],
                [attr_block("sub_block", 1)]),
        op_desc("fetch", [("X", ["acc"])], [("Out", ["fetch"])],
                [attr("col", A_INT, 0)]),
    ]
    ops_sub = [
        op_desc("increment", [("X", ["i"])], [("Out", ["i"])],
                [attr("step", A_FLOAT, 1.0)]),
        op_desc("elementwise_add", [("X", ["acc"]), ("Y", ["i"])],
                [("Out", ["acc"])], [attr("axis", A_INT, -1)]),
        op_desc("less_than", [("X", ["i"]), ("Y", ["n"])],
                [("Out", ["cond"])]),
    ]
    (tmp_path / "__model__").write_bytes(program_desc([
        block_desc(0, vars_main, ops_main),
        block_desc(1, [], ops_sub),
    ]))
    prog = load_paddle_inference_model(str(tmp_path))
    for n, expect in [(3.0, 6.0), (7.0, 28.0), (0.0, 0.0)]:
        (acc,) = prog.run({"n": np.float32(n)})
        assert float(acc) == expect, (n, acc)


def test_imported_conditional_block(tmp_path):
    vars_main = [
        var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
        var_desc("fetch", type_id=FETCH_LIST, persistable=True),
        var_desc("x", dtype=FP32, dims=(-1,)),
        var_desc("flag", dtype=BOOL, dims=()),
        var_desc("zero", dtype=FP32, dims=()),
        var_desc("s", dtype=FP32, dims=()),
        var_desc("y", dtype=FP32, dims=(-1,)),
    ]
    ops_main = [
        op_desc("feed", [("X", ["feed"])], [("Out", ["x"])],
                [attr("col", A_INT, 0)]),
        op_desc("reduce_sum", [("X", ["x"])], [("Out", ["s"])],
                [attr("keep_dim", A_BOOL, False)]),
        op_desc("fill_constant", [], [("Out", ["zero"])],
                [attr("shape", A_INTS, []), attr("value", A_FLOAT, 0.0),
                 attr("dtype", A_INT, FP32)]),
        op_desc("greater_than", [("X", ["s"]), ("Y", ["zero"])],
                [("Out", ["flag"])]),
        # default: y = x; the block overwrites with 2x when sum(x) > 0
        op_desc("assign", [("X", ["x"])], [("Out", ["y"])]),
        op_desc("conditional_block", [("Cond", ["flag"]), ("Input", ["x"])],
                [("Out", ["y"])],
                [attr_block("sub_block", 1),
                 attr("is_scalar_condition", A_BOOL, True)]),
        op_desc("fetch", [("X", ["y"])], [("Out", ["fetch"])],
                [attr("col", A_INT, 0)]),
    ]
    ops_sub = [
        op_desc("scale", [("X", ["x"])], [("Out", ["y"])],
                [attr("scale", A_FLOAT, 2.0), attr("bias", A_FLOAT, 0.0)]),
    ]
    (tmp_path / "__model__").write_bytes(program_desc([
        block_desc(0, vars_main, ops_main),
        block_desc(1, [], ops_sub),
    ]))
    prog = load_paddle_inference_model(str(tmp_path))
    pos = np.asarray([1.0, 2.0], np.float32)
    neg = np.asarray([-1.0, -2.0], np.float32)
    (y,) = prog.run({"x": pos})
    np.testing.assert_allclose(y, pos * 2)       # branch fired
    (y,) = prog.run({"x": neg})
    np.testing.assert_allclose(y, neg)           # branch skipped


def test_imported_conditional_block_non_scalar(tmp_path):
    """Proto-default is_scalar_condition=False: the sub-block runs iff the
    Cond inputs are NON-EMPTY — element values are irrelevant, and an
    empty Cond skips (conditional_block_op.h:124-128)."""
    vars_main = [
        var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
        var_desc("fetch", type_id=FETCH_LIST, persistable=True),
        var_desc("x", dtype=FP32, dims=(-1,)),
        var_desc("cond", dtype=FP32, dims=(-1,)),
        var_desc("y", dtype=FP32, dims=(-1,)),
    ]
    ops_main = [
        op_desc("feed", [("X", ["feed"])], [("Out", ["x"])],
                [attr("col", A_INT, 0)]),
        op_desc("feed", [("X", ["feed"])], [("Out", ["cond"])],
                [attr("col", A_INT, 1)]),
        op_desc("assign", [("X", ["x"])], [("Out", ["y"])]),
        # no is_scalar_condition attr: proto default (False) applies
        op_desc("conditional_block",
                [("Cond", ["cond"]), ("Input", ["x"])],
                [("Out", ["y"])], [attr_block("sub_block", 1)]),
        op_desc("fetch", [("X", ["y"])], [("Out", ["fetch"])],
                [attr("col", A_INT, 0)]),
    ]
    ops_sub = [
        op_desc("scale", [("X", ["x"])], [("Out", ["y"])],
                [attr("scale", A_FLOAT, 2.0), attr("bias", A_FLOAT, 0.0)]),
    ]
    (tmp_path / "__model__").write_bytes(program_desc([
        block_desc(0, vars_main, ops_main),
        block_desc(1, [], ops_sub),
    ]))
    prog = load_paddle_inference_model(str(tmp_path))
    x = np.asarray([1.0, 2.0], np.float32)
    # non-empty Cond of ALL-ZERO values still fires (values irrelevant)
    (y,) = prog.run({"x": x, "cond": np.zeros(3, np.float32)})
    np.testing.assert_allclose(y, x * 2)
    # empty Cond skips (no error)
    (y,) = prog.run({"x": x, "cond": np.zeros(0, np.float32)})
    np.testing.assert_allclose(y, x)


def test_round_trip_save_after_passes(tmp_path):
    """import -> optimize (passes) -> SAVE back to reference format ->
    reload: numerics identical, op list smaller, folded constants and
    pruned params synced into the written descriptors."""
    from paddle_tpu.inference.passes import run_inference_passes
    from paddle_tpu.interop import save_paddle_inference_model

    rs = np.random.RandomState(9)
    w = rs.randn(4, 4).astype(np.float32)
    c = rs.randn(4, 4).astype(np.float32)
    vars_ = [
        var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
        var_desc("fetch", type_id=FETCH_LIST, persistable=True),
        var_desc("x", dims=(-1, 4)),
        var_desc("c", dims=(4, 4), persistable=True),
        var_desc("w", dims=(4, 4), persistable=True),
        var_desc("w2", dims=(4, 4)), var_desc("h", dims=(-1, 4)),
        var_desc("hd", dims=(-1, 4)),
    ]
    ops = [
        op_desc("feed", [("X", ["feed"])], [("Out", ["x"])],
                [attr("col", A_INT, 0)]),
        op_desc("elementwise_add", [("X", ["w"]), ("Y", ["c"])],
                [("Out", ["w2"])], [attr("axis", A_INT, -1)]),  # foldable
        op_desc("mul", [("X", ["x"]), ("Y", ["w2"])], [("Out", ["h"])],
                [attr("x_num_col_dims", A_INT, 1),
                 attr("y_num_col_dims", A_INT, 1)]),
        op_desc("dropout", [("X", ["h"])], [("Out", ["hd"])],
                [attr("dropout_prob", A_FLOAT, 0.5),
                 attr("dropout_implementation", A_STRING,
                      "upscale_in_train")]),  # identity
        op_desc("fetch", [("X", ["hd"])], [("Out", ["fetch"])],
                [attr("col", A_INT, 0)]),
    ]
    src = tmp_path / "src"
    src.mkdir()
    (src / "__model__").write_bytes(
        program_desc([block_desc(0, vars_, ops)]))
    with open(src / "__params__", "wb") as f:
        for arr in (c, w):  # sorted names
            f.write(lod_tensor_stream(arr))

    prog = load_paddle_inference_model(str(src),
                                       params_filename="__params__")
    x = rs.randn(4, 4).astype(np.float32)
    (before,) = prog.run({"x": x})
    n_ops = len(prog.blocks[0].ops)
    run_inference_passes(prog)

    out_dir = tmp_path / "optimized"
    save_paddle_inference_model(prog, str(out_dir))
    prog2 = load_paddle_inference_model(str(out_dir),
                                        params_filename="__params__")
    (after,) = prog2.run({"x": x})
    np.testing.assert_allclose(after, before, rtol=1e-6)
    np.testing.assert_allclose(after, x @ (w + c), rtol=1e-6)
    assert len(prog2.blocks[0].ops) < n_ops
    # folded constant w2 became a persistable; w and c were pruned
    assert "w2" in prog2.params and "c" not in prog2.params
    assert prog2.feed_names == ["x"]


def test_round_trip_while_program(tmp_path):
    """Multi-block (control flow) programs serialize losslessly too —
    attr types (incl. BLOCK) survive the round trip."""
    from paddle_tpu.interop import save_paddle_inference_model

    # reuse the while artifact from test_imported_while_loop
    vars_main = [
        var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
        var_desc("fetch", type_id=FETCH_LIST, persistable=True),
        var_desc("n", dtype=FP32, dims=()),
        var_desc("i", dtype=FP32, dims=()),
        var_desc("acc", dtype=FP32, dims=()),
        var_desc("cond", dtype=BOOL, dims=()),
    ]
    ops_main = [
        op_desc("feed", [("X", ["feed"])], [("Out", ["n"])],
                [attr("col", A_INT, 0)]),
        op_desc("fill_constant", [], [("Out", ["i"])],
                [attr("shape", A_INTS, []), attr("value", A_FLOAT, 0.0),
                 attr("dtype", A_INT, FP32)]),
        op_desc("fill_constant", [], [("Out", ["acc"])],
                [attr("shape", A_INTS, []), attr("value", A_FLOAT, 0.0),
                 attr("dtype", A_INT, FP32)]),
        op_desc("less_than", [("X", ["i"]), ("Y", ["n"])],
                [("Out", ["cond"])]),
        op_desc("while",
                [("X", ["i", "acc", "n"]), ("Condition", ["cond"])],
                [("Out", ["i", "acc"])],
                [attr_block("sub_block", 1)]),
        op_desc("fetch", [("X", ["acc"])], [("Out", ["fetch"])],
                [attr("col", A_INT, 0)]),
    ]
    ops_sub = [
        op_desc("increment", [("X", ["i"])], [("Out", ["i"])],
                [attr("step", A_FLOAT, 1.0)]),
        op_desc("elementwise_add", [("X", ["acc"]), ("Y", ["i"])],
                [("Out", ["acc"])], [attr("axis", A_INT, -1)]),
        op_desc("less_than", [("X", ["i"]), ("Y", ["n"])],
                [("Out", ["cond"])]),
    ]
    (tmp_path / "src").mkdir()
    (tmp_path / "src/__model__").write_bytes(program_desc([
        block_desc(0, vars_main, ops_main),
        block_desc(1, [], ops_sub),
    ]))
    prog = load_paddle_inference_model(str(tmp_path / "src"))
    save_paddle_inference_model(prog, str(tmp_path / "dst"),
                                params_filename=None)
    prog2 = load_paddle_inference_model(str(tmp_path / "dst"))
    for n, expect in [(4.0, 10.0), (0.0, 0.0)]:
        (acc,) = prog2.run({"n": np.float32(n)})
        assert float(acc) == expect


def test_round_trip_conv_bn_folded_model(tmp_path):
    """Serializing after fold_conv_bn (pass-synthesized ops + params) —
    and saving must NOT mutate the in-memory program."""
    import copy

    from paddle_tpu.inference.passes import run_inference_passes
    from paddle_tpu.interop import (
        load_paddle_inference_model, save_paddle_inference_model,
    )

    rs = np.random.RandomState(11)
    k = rs.randn(4, 3, 3, 3).astype(np.float32)
    s = rs.rand(4).astype(np.float32) + 0.5
    b = rs.randn(4).astype(np.float32)
    m = rs.randn(4).astype(np.float32) * 0.1
    v = rs.rand(4).astype(np.float32) + 0.5
    vars_ = [
        var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
        var_desc("fetch", type_id=FETCH_LIST, persistable=True),
        var_desc("img", dims=(-1, 3, 8, 8)),
        var_desc("k", dims=(4, 3, 3, 3), persistable=True),
        var_desc("bn_s", dims=(4,), persistable=True),
        var_desc("bn_b", dims=(4,), persistable=True),
        var_desc("bn_m", dims=(4,), persistable=True),
        var_desc("bn_v", dims=(4,), persistable=True),
        var_desc("c0", dims=(-1, 4, 8, 8)), var_desc("c1", dims=(-1, 4, 8, 8)),
    ]
    ops = [
        op_desc("feed", [("X", ["feed"])], [("Out", ["img"])],
                [attr("col", A_INT, 0)]),
        op_desc("conv2d", [("Input", ["img"]), ("Filter", ["k"])],
                [("Output", ["c0"])],
                [attr("strides", A_INTS, [1, 1]),
                 attr("paddings", A_INTS, [1, 1]),
                 attr("dilations", A_INTS, [1, 1]),
                 attr("groups", A_INT, 1)]),
        op_desc("batch_norm",
                [("X", ["c0"]), ("Scale", ["bn_s"]), ("Bias", ["bn_b"]),
                 ("Mean", ["bn_m"]), ("Variance", ["bn_v"])],
                [("Y", ["c1"])], [attr("epsilon", A_FLOAT, 1e-5)]),
        op_desc("fetch", [("X", ["c1"])], [("Out", ["fetch"])],
                [attr("col", A_INT, 0)]),
    ]
    src = tmp_path / "src"
    src.mkdir()
    (src / "__model__").write_bytes(
        program_desc([block_desc(0, vars_, ops)]))
    with open(src / "__params__", "wb") as f:
        for arr in (b, m, s, v, k):
            f.write(lod_tensor_stream(arr))

    prog = load_paddle_inference_model(str(src),
                                       params_filename="__params__")
    x = rs.randn(2, 3, 8, 8).astype(np.float32)
    (before,) = prog.run({"img": x})
    run_inference_passes(prog)
    vars_before_save = dict(prog.blocks[0].vars)
    names_before_save = list(prog.persistable_names)

    save_paddle_inference_model(prog, str(tmp_path / "dst"))
    # the saved-from program is untouched
    assert prog.blocks[0].vars == vars_before_save
    assert prog.persistable_names == names_before_save

    prog2 = load_paddle_inference_model(str(tmp_path / "dst"),
                                        params_filename="__params__")
    (after,) = prog2.run({"img": x})
    np.testing.assert_allclose(after, before, rtol=1e-4, atol=1e-5)
    assert "batch_norm" not in [o.type for o in prog2.blocks[0].ops]


def _interp_artifact(tmp_path, op_type, attrs, in_shape=(-1, 3, 5, 7),
                     out_shape=(-1, 3, -1, -1), extra_inputs=(),
                     extra_vars=()):
    vars_ = [
        var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
        var_desc("fetch", type_id=FETCH_LIST, persistable=True),
        var_desc("img", dims=in_shape),
        var_desc("out", dims=out_shape),
    ] + list(extra_vars)
    ops = [
        op_desc("feed", [("X", ["feed"])], [("Out", ["img"])],
                [attr("col", A_INT, 0)]),
        op_desc(op_type, [("X", ["img"])] + list(extra_inputs),
                [("Out", ["out"])], attrs),
        op_desc("fetch", [("X", ["out"])], [("Out", ["fetch"])],
                [attr("col", A_INT, 0)]),
    ]
    (tmp_path / "__model__").write_bytes(
        program_desc([block_desc(0, vars_, ops)]))
    return load_paddle_inference_model(str(tmp_path))


def _np_bilinear_ref(x, oh, ow, align_corners, align_mode):
    """Independent numpy oracle of interpolate_op.h BilinearInterpFwd."""
    n, c, ih, iw = x.shape
    out = np.zeros((n, c, oh, ow), np.float64)
    for j in range(oh):
        for i in range(ow):
            if align_corners:
                sh = j * (ih - 1) / max(oh - 1, 1)
                sw = i * (iw - 1) / max(ow - 1, 1)
            elif align_mode == 1:
                sh, sw = j * ih / oh, i * iw / ow
            else:
                sh = (j + 0.5) * ih / oh - 0.5
                sw = (i + 0.5) * iw / ow - 0.5
            sh = min(max(sh, 0.0), ih - 1)
            sw = min(max(sw, 0.0), iw - 1)
            h0, w0 = int(np.floor(sh)), int(np.floor(sw))
            h1, w1 = min(h0 + 1, ih - 1), min(w0 + 1, iw - 1)
            fh, fw = sh - h0, sw - w0
            out[:, :, j, i] = (
                x[:, :, h0, w0] * (1 - fh) * (1 - fw)
                + x[:, :, h1, w0] * fh * (1 - fw)
                + x[:, :, h0, w1] * (1 - fh) * fw
                + x[:, :, h1, w1] * fh * fw)
    return out.astype(np.float32)


class TestInterpFamily:
    """VERDICT r3 next #10: the reference-DEFAULT interp modes
    (align_mode=1 origin-aligned bilinear, floor-indexed nearest at any
    scale, align_corners) import without re-export."""

    def _x(self):
        return np.random.RandomState(11).randn(2, 3, 5, 7).astype("f4")

    def test_bilinear_align_mode_1_default(self, tmp_path):
        # NO align_mode attr: the proto default (1) applies
        prog = _interp_artifact(tmp_path, "bilinear_interp_v2",
                                [attr("out_h", A_INT, 9),
                                 attr("out_w", A_INT, 11)])
        x = self._x()
        (got,) = prog.run({"img": x})
        np.testing.assert_allclose(
            got, _np_bilinear_ref(x, 9, 11, False, 1), rtol=1e-5,
            atol=1e-6)

    def test_bilinear_align_mode_0_matches_torch(self, tmp_path):
        torch = pytest.importorskip("torch")
        prog = _interp_artifact(tmp_path, "bilinear_interp_v2",
                                [attr("out_h", A_INT, 8),
                                 attr("out_w", A_INT, 10),
                                 attr("align_mode", A_INT, 0)])
        x = self._x()
        (got,) = prog.run({"img": x})
        ref = torch.nn.functional.interpolate(
            torch.from_numpy(x), size=(8, 10), mode="bilinear",
            align_corners=False).numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_bilinear_align_corners_matches_torch(self, tmp_path):
        torch = pytest.importorskip("torch")
        prog = _interp_artifact(tmp_path, "bilinear_interp_v2",
                                [attr("out_h", A_INT, 9),
                                 attr("out_w", A_INT, 13),
                                 attr("align_corners", A_BOOL, True)])
        x = self._x()
        (got,) = prog.run({"img": x})
        ref = torch.nn.functional.interpolate(
            torch.from_numpy(x), size=(9, 13), mode="bilinear",
            align_corners=True).numpy()
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_nearest_non_integer_scale(self, tmp_path):
        prog = _interp_artifact(tmp_path, "nearest_interp_v2",
                                [attr("out_h", A_INT, 7),
                                 attr("out_w", A_INT, 9)])
        x = self._x()
        (got,) = prog.run({"img": x})
        idx_h = np.minimum(np.arange(7) * 5 // 7, 4)
        idx_w = np.minimum(np.arange(9) * 7 // 9, 6)
        ref = x[:, :, idx_h][:, :, :, idx_w]
        np.testing.assert_array_equal(got, ref)

    def test_out_size_tensor_input(self, tmp_path):
        prog = _interp_artifact(
            tmp_path, "bilinear_interp_v2", [],
            extra_inputs=[("OutSize", ["osz"])],
            extra_vars=[var_desc("osz", dtype=INT32, dims=(2,))])
        x = self._x()
        (got,) = prog.run({"img": x,
                           "osz": np.asarray([6, 8], np.int32)})
        np.testing.assert_allclose(
            got, _np_bilinear_ref(x, 6, 8, False, 1), rtol=1e-5,
            atol=1e-6)


class TestTopKEdges:
    def _artifact(self, tmp_path, attrs, extra_inputs=(), extra_vars=()):
        vars_ = [
            var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
            var_desc("fetch", type_id=FETCH_LIST, persistable=True),
            var_desc("x", dims=(-1, 6)),
            var_desc("v", dims=(-1, -1)), var_desc("ix", dims=(-1, -1)),
        ] + list(extra_vars)
        ops = [
            op_desc("feed", [("X", ["feed"])], [("Out", ["x"])],
                    [attr("col", A_INT, 0)]),
            op_desc("top_k_v2", [("X", ["x"])] + list(extra_inputs),
                    [("Out", ["v"]), ("Indices", ["ix"])], attrs),
            op_desc("fetch", [("X", ["v"])], [("Out", ["fetch"])],
                    [attr("col", A_INT, 0)]),
            op_desc("fetch", [("X", ["ix"])], [("Out", ["fetch"])],
                    [attr("col", A_INT, 1)]),
        ]
        (tmp_path / "__model__").write_bytes(
            program_desc([block_desc(0, vars_, ops)]))
        return load_paddle_inference_model(str(tmp_path))

    def test_tensor_k_input(self, tmp_path):
        prog = self._artifact(
            tmp_path, [], extra_inputs=[("K", ["kt"])],
            extra_vars=[var_desc("kt", dtype=INT32, dims=(1,))])
        x = np.random.RandomState(3).randn(4, 6).astype("f4")
        v, ix = prog.run({"x": x, "kt": np.asarray([3], np.int32)})
        ref = np.sort(x, axis=-1)[:, ::-1][:, :3]
        np.testing.assert_allclose(v, ref, rtol=1e-6)
        assert v.shape == (4, 3) and ix.shape == (4, 3)

    def test_smallest_and_axis(self, tmp_path):
        prog = self._artifact(tmp_path,
                              [attr("k", A_INT, 2),
                               attr("axis", A_INT, 0),
                               attr("largest", A_BOOL, False)])
        x = np.random.RandomState(4).randn(5, 6).astype("f4")
        v, ix = prog.run({"x": x})
        ref = np.sort(x, axis=0)[:2, :]
        np.testing.assert_allclose(v, ref, rtol=1e-6)
        assert v.shape == (2, 6)


def test_nearest_align_corners_rounds_half_up(tmp_path):
    """5 -> 9 with align_corners: src coords land exactly on .5 at output
    rows 1,3,5,7; the reference's static_cast<int>(ratio*j + 0.5) rounds
    half UP -> indices [0,1,1,2,2,3,3,4,4] (np.rint's half-to-even would
    wrongly give [0,0,1,2,2,2,3,4,4])."""
    prog = _interp_artifact(tmp_path, "nearest_interp_v2",
                            [attr("out_h", A_INT, 9),
                             attr("out_w", A_INT, 9),
                             attr("align_corners", A_BOOL, True)],
                            in_shape=(-1, 1, 5, 5))
    x = np.arange(2 * 1 * 5 * 5, dtype=np.float32).reshape(2, 1, 5, 5)
    (got,) = prog.run({"img": x})
    idx = np.array([0, 1, 1, 2, 2, 3, 3, 4, 4])
    ref = x[:, :, idx][:, :, :, idx]
    np.testing.assert_array_equal(got, ref)
