// C++-only train demo (reference: paddle/fluid/train/demo/demo_trainer.cc)
//
// Runs a SERIALIZED fit-a-line training program with no Python script:
// main() lives here, the binary embeds the CPython runtime and drives the
// paddle_tpu framework purely through the CPython C API (imports, method
// calls, buffer construction) — the TPU-framework analogue of the
// reference linking libpaddle_fluid and calling framework::Executor::Run.
// The compute itself still executes through jax/XLA, exactly as the
// reference demo's kernels execute through its op library.
//
// Usage: demo_trainer <model_dir> [steps]
//   where <model_dir> holds "main_program" and "startup_program" files
//   written by paddle_tpu.proto.save_program, with data vars "x" [B,13]
//   and "y" [B,1] (the reference demo's fit-a-line contract) and a
//   "mean" op producing the loss.

#include <Python.h>

#include <cstdio>
#include <cstdlib>
#include <string>

namespace {

void Fatal(const char* what) {
  std::fprintf(stderr, "demo_trainer: %s\n", what);
  if (PyErr_Occurred()) PyErr_Print();
  std::exit(1);
}

PyObject* Import(const char* name) {
  PyObject* m = PyImport_ImportModule(name);
  if (!m) Fatal((std::string("cannot import ") + name).c_str());
  return m;
}

// call obj.method(args...) with a new reference result
PyObject* Call(PyObject* obj, const char* method, PyObject* args) {
  PyObject* fn = PyObject_GetAttrString(obj, method);
  if (!fn) Fatal((std::string("no attribute ") + method).c_str());
  PyObject* res = PyObject_CallObject(fn, args);
  Py_DECREF(fn);
  Py_XDECREF(args);
  if (!res) Fatal((std::string("call failed: ") + method).c_str());
  return res;
}

// find the loss var name: first "mean" op's Out (reference demo_trainer.cc
// scans Block(0).AllOps() the same way)
std::string FindLossName(PyObject* program) {
  PyObject* block = Call(program, "global_block", PyTuple_New(0));
  PyObject* ops = PyObject_GetAttrString(block, "ops");
  if (!ops) Fatal("block has no ops");
  Py_ssize_t n = PyList_Size(ops);
  std::string loss;
  for (Py_ssize_t i = 0; i < n && loss.empty(); ++i) {
    PyObject* op = PyList_GetItem(ops, i);  // borrowed
    PyObject* type = PyObject_GetAttrString(op, "type");
    if (type && PyUnicode_Check(type) &&
        std::string(PyUnicode_AsUTF8(type)) == "mean") {
      PyObject* outs = Call(op, "output", Py_BuildValue("(s)", "Out"));
      if (PyList_Size(outs) > 0)
        loss = PyUnicode_AsUTF8(PyList_GetItem(outs, 0));
      Py_DECREF(outs);
    }
    Py_XDECREF(type);
  }
  Py_DECREF(ops);
  Py_DECREF(block);
  if (loss.empty()) Fatal("no mean op found — is this fit-a-line?");
  return loss;
}

}  // namespace

int main(int argc, char** argv) {
  const char* dir = argc > 1 ? argv[1] : ".";
  const int steps = argc > 2 ? std::atoi(argv[2]) : 10;
  if (steps < 2) Fatal("steps must be >= 2 (loss-decrease check)");
  const int batch = 2;  // reference demo feeds x[2,13], y[2,1]

  Py_Initialize();

  if (std::getenv("PADDLE_TPU_DEMO_FORCE_CPU")) {
    // tests force the CPU backend in-process, before the framework's
    // first device use
    PyObject* jaxm = Import("jax");
    PyObject* cfg = PyObject_GetAttrString(jaxm, "config");
    if (!cfg) Fatal("jax.config missing");
    Py_DECREF(
        Call(cfg, "update", Py_BuildValue("(ss)", "jax_platforms", "cpu")));
    Py_DECREF(cfg);
  }

  PyObject* proto = Import("paddle_tpu.proto");
  PyObject* fluid = Import("paddle_tpu");
  PyObject* np = Import("numpy");

  std::string main_path = std::string(dir) + "/main_program";
  std::string startup_path = std::string(dir) + "/startup_program";
  PyObject* main_prog =
      Call(proto, "load_program", Py_BuildValue("(s)", main_path.c_str()));
  PyObject* startup_prog = Call(
      proto, "load_program", Py_BuildValue("(s)", startup_path.c_str()));

  std::string loss_name = FindLossName(main_prog);

  // exe = fluid.Executor(fluid.CPUPlace()); exe.run(startup)
  PyObject* place = Call(fluid, "CPUPlace", PyTuple_New(0));
  PyObject* exe = Call(fluid, "Executor", Py_BuildValue("(O)", place));
  Py_DECREF(Call(exe, "run", Py_BuildValue("(O)", startup_prog)));

  // synthetic fit-a-line batch, built through the numpy API:
  // x = arange(batch*13).reshape(batch,13).astype(float32) / 26.0
  PyObject* x = Call(np, "arange", Py_BuildValue("(i)", batch * 13));
  x = Call(x, "reshape", Py_BuildValue("(ii)", batch, 13));
  x = Call(x, "astype", Py_BuildValue("(s)", "float32"));
  x = PyNumber_TrueDivide(x, PyFloat_FromDouble(26.0));
  if (!x) Fatal("x construction failed");
  PyObject* y = Call(np, "arange", Py_BuildValue("(i)", batch));
  y = Call(y, "reshape", Py_BuildValue("(ii)", batch, 1));
  y = Call(y, "astype", Py_BuildValue("(s)", "float32"));

  PyObject* feed = PyDict_New();
  PyDict_SetItemString(feed, "x", x);
  PyDict_SetItemString(feed, "y", y);
  PyObject* fetch = PyList_New(1);
  PyList_SetItem(fetch, 0, PyUnicode_FromString(loss_name.c_str()));

  double first = 0.0, last = 0.0;
  for (int i = 0; i < steps; ++i) {
    // exe.run(main_prog, feed=feed, fetch_list=[loss])
    PyObject* kwargs = PyDict_New();
    PyDict_SetItemString(kwargs, "feed", feed);
    PyDict_SetItemString(kwargs, "fetch_list", fetch);
    PyObject* run = PyObject_GetAttrString(exe, "run");
    PyObject* args = Py_BuildValue("(O)", main_prog);
    PyObject* out = PyObject_Call(run, args, kwargs);
    Py_DECREF(run);
    Py_DECREF(args);
    Py_DECREF(kwargs);
    if (!out) Fatal("training step failed");
    PyObject* loss_arr = PyList_GetItem(out, 0);  // borrowed
    PyObject* loss_f = Call(loss_arr, "item", PyTuple_New(0));
    double loss = PyFloat_AsDouble(loss_f);
    Py_DECREF(loss_f);
    Py_DECREF(out);
    std::printf("step: %d loss: %f\n", i, loss);
    if (i == 0) first = loss;
    last = loss;
  }

  if (!(last < first)) Fatal("loss did not decrease");
  std::printf("demo_trainer ok: loss %f -> %f\n", first, last);

  Py_DECREF(feed);
  Py_DECREF(fetch);
  Py_Finalize();
  return 0;
}
