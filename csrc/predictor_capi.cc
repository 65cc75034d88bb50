// C inference API implementation (see pt_predictor.h).
//
// Embeds CPython (the csrc/standalone_trainer.cc pattern): the XLA
// compute path is identical to the Python Predictor's — fixed-signature
// compiled executables with donated, device-resident parameters
// (paddle_tpu/inference.py). Reference counterpart:
// paddle/fluid/inference/api/api.cc (NativePaddlePredictor C surface).

#include "pt_predictor.h"

#include <Python.h>

#include <cstring>
#include <string>
#include <vector>

namespace {

std::string g_error;

void SetErrorFromPython() {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  PyErr_NormalizeException(&type, &value, &tb);
  g_error = "python error";
  if (value != nullptr) {
    PyObject* s = PyObject_Str(value);
    if (s != nullptr) {
      const char* c = PyUnicode_AsUTF8(s);
      if (c != nullptr) g_error = c;
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
}

bool EnsurePython() {
  if (Py_IsInitialized()) return true;
  Py_Initialize();
  // Make the repo importable: PT_REPO env or cwd (same contract as the
  // standalone trainer).
  const char* repo = std::getenv("PT_REPO");
  std::string code =
      "import sys, os\n"
      "sys.path.insert(0, os.environ.get('PT_REPO', os.getcwd()))\n";
  (void)repo;
  if (PyRun_SimpleString(code.c_str()) != 0) {
    g_error = "python bootstrap failed";
    return false;
  }
  return true;
}

struct Output {
  Py_buffer view;        // holds the float32 numpy buffer alive
  std::vector<long long> shape;
  bool held = false;
};

}  // namespace

struct pt_predictor {
  PyObject* globals = nullptr;  // namespace holding PRED / helpers
  std::vector<Output> outputs;

  void ReleaseOutputs() {
    for (auto& o : outputs) {
      if (o.held) PyBuffer_Release(&o.view);
    }
    outputs.clear();
  }
};

extern "C" {

const char* pt_predictor_error(void) { return g_error.c_str(); }

pt_predictor* pt_predictor_create(const char* model_dir) {
  if (!EnsurePython()) return nullptr;
  PyObject* globals = PyDict_New();
  PyDict_SetItemString(globals, "__builtins__", PyEval_GetBuiltins());
  PyObject* dir_obj = PyUnicode_FromString(model_dir);
  PyDict_SetItemString(globals, "MODEL_DIR", dir_obj);  // does not steal
  Py_DECREF(dir_obj);
  static const char kCreate[] = R"PY(
import numpy as np
from paddle_tpu.inference import Config, create_predictor
PRED = create_predictor(Config(MODEL_DIR))
_NP = np
_DTYPES = {0: np.float32, 1: np.int64, 2: np.int32}

def _RUN(feed_specs):
    # feed_specs: list of (name, memoryview, dtype_code, shape_tuple)
    feed = {}
    for name, mv, code, shape in feed_specs:
        arr = np.frombuffer(mv, dtype=_DTYPES[code]).reshape(shape).copy()
        feed[name] = arr
    outs = PRED.run(feed)
    return [np.ascontiguousarray(np.asarray(o), dtype=np.float32)
            for o in outs]
)PY";
  PyObject* r = PyRun_String(kCreate, Py_file_input, globals, globals);
  if (r == nullptr) {
    SetErrorFromPython();
    Py_DECREF(globals);
    return nullptr;
  }
  Py_DECREF(r);
  pt_predictor* p = new pt_predictor();
  p->globals = globals;
  return p;
}

void pt_predictor_destroy(pt_predictor* p) {
  if (p == nullptr) return;
  p->ReleaseOutputs();
  Py_XDECREF(p->globals);
  delete p;
}

int pt_predictor_run(pt_predictor* p, int n_inputs,
                     const char* const* names, const void* const* data,
                     const int* dtypes, const int* ranks,
                     const long long* shapes) {
  static const size_t kDtypeSize[] = {4, 8, 4};
  PyObject* specs = PyList_New(n_inputs);
  const long long* dim = shapes;
  for (int i = 0; i < n_inputs; ++i) {
    long long numel = 1;
    PyObject* shape = PyTuple_New(ranks[i]);
    for (int d = 0; d < ranks[i]; ++d, ++dim) {
      numel *= *dim;
      PyTuple_SetItem(shape, d, PyLong_FromLongLong(*dim));
    }
    if (dtypes[i] < 0 || dtypes[i] > 2) {
      Py_DECREF(shape);
      Py_DECREF(specs);
      g_error = "unknown dtype code";
      return 1;
    }
    PyObject* mv = PyMemoryView_FromMemory(
        const_cast<char*>(static_cast<const char*>(data[i])),
        numel * kDtypeSize[dtypes[i]], PyBUF_READ);
    // PyTuple_Pack increfs its arguments: every temporary must be
    // released here or each call leaks one ref per input (unbounded
    // growth in a steady-state serving loop).
    PyObject* name_obj = PyUnicode_FromString(names[i]);
    PyObject* code_obj = PyLong_FromLong(dtypes[i]);
    PyObject* spec = PyTuple_Pack(4, name_obj, mv, code_obj, shape);
    Py_DECREF(name_obj);
    Py_DECREF(code_obj);
    Py_DECREF(mv);
    Py_DECREF(shape);
    PyList_SetItem(specs, i, spec);  // steals spec
  }
  PyObject* run_fn = PyDict_GetItemString(p->globals, "_RUN");  // borrowed
  PyObject* outs = PyObject_CallFunctionObjArgs(run_fn, specs, nullptr);
  Py_DECREF(specs);
  if (outs == nullptr) {
    SetErrorFromPython();
    return 1;
  }
  p->ReleaseOutputs();
  Py_ssize_t n = PyList_Size(outs);
  p->outputs.resize(static_cast<size_t>(n));
  for (Py_ssize_t i = 0; i < n; ++i) {
    PyObject* arr = PyList_GetItem(outs, i);  // borrowed
    Output& o = p->outputs[static_cast<size_t>(i)];
    if (PyObject_GetBuffer(arr, &o.view, PyBUF_CONTIG_RO | PyBUF_FORMAT) !=
        0) {
      SetErrorFromPython();
      Py_DECREF(outs);
      p->ReleaseOutputs();
      return 1;
    }
    o.held = true;  // Py_buffer keeps the array alive after outs dies
    o.shape.assign(o.view.shape, o.view.shape + o.view.ndim);
  }
  Py_DECREF(outs);
  return 0;
}

int pt_predictor_num_outputs(pt_predictor* p) {
  return static_cast<int>(p->outputs.size());
}

int pt_predictor_output_rank(pt_predictor* p, int i) {
  return static_cast<int>(p->outputs[static_cast<size_t>(i)].shape.size());
}

const long long* pt_predictor_output_shape(pt_predictor* p, int i) {
  return p->outputs[static_cast<size_t>(i)].shape.data();
}

const float* pt_predictor_output_data(pt_predictor* p, int i,
                                      long long* numel) {
  const Output& o = p->outputs[static_cast<size_t>(i)];
  long long n = 1;
  for (long long d : o.shape) n *= d;
  if (numel != nullptr) *numel = n;
  return static_cast<const float*>(o.view.buf);
}

}  // extern "C"
